"""95th percentile of how late the load generator submitted a request
behind its due time (host clock): a starved generator is not a fast
server."""


def read(r):
    return r["counters"]["lag_p95_ms"]
