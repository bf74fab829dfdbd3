"""``sce_gather`` forward and backward in the LM train step: their
least time per step at the model's width ÷ the summed device time of
``sce_gather_fwd`` and ``sce_gather_bwd`` events."""
from harness import readers, work


def read(r):
    c = r["counters"]
    shape = (c["n_b"], c["b_x"], c["b_y"], r["config"]["hidden_size"])
    works = [work.sce_gather_fwd(*shape), work.sce_gather_bwd(*shape)]
    return readers.roofline(r, ["sce_gather_fwd", "sce_gather_bwd"], works,
                            c["steps"])
