"""Share of the traced train window with no operation on the device."""
from harness import readers


def read(r):
    return readers.idle_share(r)
