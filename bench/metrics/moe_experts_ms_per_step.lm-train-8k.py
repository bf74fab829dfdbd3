"""Device time per step under the LM train step's ``moe/experts`` scope:
the held experts' grouped matmuls and the scatter back, forward and
backward; the union of its operations' intervals (``layer_s`` of
``kinds/lm_train.py``)."""
from harness import scopes


def read(r):
    return scopes.ms_per_step(r, "layer_s", "moe/experts")
