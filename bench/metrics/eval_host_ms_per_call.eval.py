"""Device idle time per eval call in gaps whose innermost host span is one
of ``evaluate_streaming``'s ``repro.eval.*`` spans (encode, score,
fold): the device waiting on the eval path's host work
(``idle_by_span`` of ``harness/scopes.py``)."""


def read(r):
    t, calls = r["trace"], r["counters"].get("calls")
    if not t or "idle_by_span" not in t or not calls:
        return None
    idle = sum(v for k, v in t["idle_by_span"].items()
               if k.startswith("repro.eval."))
    return 1e3 * idle / calls
