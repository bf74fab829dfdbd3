"""Share of the bucket rows the server ran that carried a request: requests
served ÷ summed bucket sizes (the window's change of ``health()``'s
``batch_rows``); the rest is padding up to the bucket."""


def read(r):
    c = r["counters"]
    if not c.get("batch_rows"):
        return None
    return 100.0 * c["served"] / c["batch_rows"]
