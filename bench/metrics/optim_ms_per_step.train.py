"""Device time per step under the train step's ``optim`` scope: the AdamW
update, the finiteness check and the skip select, as the union of their
operations' intervals (``scope_s`` of ``harness/scopes.py``)."""
from harness import scopes


def read(r):
    return scopes.ms_per_step(r, "scope_s", "optim")
