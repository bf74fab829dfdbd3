"""Device idle time per micro-batch in gaps whose innermost host span is
one of the serve worker's ``repro.serve.*`` spans other than
``repro.serve.idle`` (padding, the program call and readback,
replying): the device waiting on the server's host path, not on
arrivals (``idle_by_span`` of ``harness/scopes.py``)."""


def read(r):
    t = r["trace"]
    batches = (t or {}).get("span_n", {}).get("repro.serve.batch")
    if not batches:
        return None
    idle = sum(v for k, v in t["idle_by_span"].items()
               if k.startswith("repro.serve.") and k != "repro.serve.idle")
    return 1e3 * idle / batches
