"""``eval_fused`` and its ``eval_tgt_gather`` pre-stage: the least time
of one catalog sweep and one target gather per call ÷ the summed device
time of their events."""
from harness import readers, work


def read(r):
    c, d = r["counters"], r["config"]["sizes"]["d_model"]
    works = [work.eval_fused(c["rows"], c["catalog"], d, c["k"]),
             work.eval_tgt_gather(c["rows"], d)]
    return readers.roofline(r, ["eval_fused", "eval_tgt_gather"], works,
                            c["calls"])
