"""Device time per step under the LM train step's ``mla`` scope: latent
attention's projections, the q-chunked causal attention over the
reconstructed keys and values and the output projection, forward and
backward; the union of its operations' intervals (``layer_s`` of
``kinds/lm_train.py``)."""
from harness import scopes


def read(r):
    return scopes.ms_per_step(r, "layer_s", "mla")
