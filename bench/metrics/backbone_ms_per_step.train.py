"""Device time per step under the train step's ``backbone`` scope: the item
embedding lookup and the SASRec encoder, forward and backward, as the
union of their operations' intervals (``scope_s`` of
``harness/scopes.py``)."""
from harness import scopes


def read(r):
    return scopes.ms_per_step(r, "scope_s", "backbone")
