"""``sce_gather`` forward and backward in the train step: their least
time per step ÷ the summed device time of ``sce_gather_fwd`` and
``sce_gather_bwd`` events."""
from harness import readers, work


def read(r):
    c, d = r["counters"], r["config"]["sizes"]["d_model"]
    shape = (c["n_b"], c["b_x"], c["b_y"], d)
    works = [work.sce_gather_fwd(*shape), work.sce_gather_bwd(*shape)]
    return readers.roofline(r, ["sce_gather_fwd", "sce_gather_bwd"], works,
                            c["steps"])
