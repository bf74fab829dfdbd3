"""Host time in the program's ``repro.data`` batch generator per step
(host clock around ``next_batch``)."""


def read(r):
    c = r["counters"]
    return 1e3 * c["data_s"] / c["steps"] if c["steps"] else None
