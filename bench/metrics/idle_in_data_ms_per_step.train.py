"""Device idle time per step in gaps whose innermost host span is the
program's ``repro.data.next_batch``: the device waiting on the batch
generator (``idle_by_span`` of ``harness/scopes.py``)."""
from harness import scopes


def read(r):
    return scopes.ms_per_step(r, "idle_by_span", "repro.data.next_batch")
