"""``mips_topk`` in the train step: the least time of its two sweeps per
step (bucket centres against the positions, top ``b_x``, and against
the catalog, top ``b_y``) ÷ the summed device time of its events."""
from harness import readers, work


def read(r):
    c, d = r["counters"], r["config"]["sizes"]["d_model"]
    works = [work.mips_topk(c["n_b"], c["positions"], d, c["b_x"],
                            masked=True),
             work.mips_topk(c["n_b"], c["catalog"], d, c["b_y"])]
    return readers.roofline(r, ["mips_topk"], works, c["steps"])
