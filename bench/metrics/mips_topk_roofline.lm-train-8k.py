"""``mips_topk`` in the LM train step: the least time of its two sweeps
per step (bucket centres against the sequence's positions, top ``b_x``,
and against the vocabulary slice, top ``b_y``) at the model's width ÷
the summed device time of its events."""
from harness import readers, work


def read(r):
    c, cfg = r["counters"], r["config"]
    d = cfg["hidden_size"]
    works = [work.mips_topk(c["n_b"], c["tokens"], d, c["b_x"],
                            masked=True),
             work.mips_topk(c["n_b"], cfg["vocab_size"], d, c["b_y"])]
    return readers.roofline(r, ["mips_topk"], works, c["steps"])
