"""Device time per step under the LM train step's ``moe/route`` scope:
the router (gate at highest, biased top-k, balance loss) and the sort
and permute of the held assignments, forward and backward; the union of
its operations' intervals (``layer_s`` of ``kinds/lm_train.py``)."""
from harness import scopes


def read(r):
    return scopes.ms_per_step(r, "layer_s", "moe/route")
