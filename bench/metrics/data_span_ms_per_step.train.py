"""Mean host time of the program's ``repro.data.next_batch`` span, one a
step, on the profiler's clock (``span_s`` ÷ ``span_n`` of
``harness/scopes.py``)."""
from harness import scopes


def read(r):
    return scopes.mean_span_ms(r, "repro.data.next_batch")
