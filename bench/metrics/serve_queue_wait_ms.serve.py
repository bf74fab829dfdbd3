"""Mean time a served request waited in ``RetrievalServer``'s queue, from
``submit()`` to pick-up by the worker (host clock; the window's change
of ``health()``'s ``queue_wait_s`` over the requests served)."""


def read(r):
    c = r["counters"]
    if "queue_wait_s" not in c or not c.get("served"):
        return None
    return 1e3 * c["queue_wait_s"] / c["served"]
