"""Device time per step under the train step's ``gather_loss`` scope: the
positive logits, ``sce_gather`` forward and backward and the loss's
aggregation, as the union of their operations' intervals (``scope_s`` of
``harness/scopes.py``)."""
from harness import scopes


def read(r):
    return scopes.ms_per_step(r, "scope_s", "gather_loss")
