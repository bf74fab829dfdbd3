"""Device time per step under the train step's ``select`` scope: the Mix
projection and both ``mips_topk`` selections (``select/positions``,
K = b_x, and ``select/catalog``, K = b_y), as the union of their
operations' intervals (``scope_s`` of ``harness/scopes.py``)."""
from harness import scopes


def read(r):
    return scopes.ms_per_step(r, "scope_s", "select")
