"""``mips_topk`` in the serve programs: the least time of the catalog
sweeps of the traced window (one per ``mips_topk`` event, the score rows
of the requests served) ÷ the summed device time of those events."""
from harness import readers, work


def read(r):
    c, t = r["counters"], r["trace"]
    if not t or not t["kernel_n"].get("mips_topk"):
        return None
    d, k = r["config"]["sizes"]["d_model"], r["traffic"]["top_k"]
    calls = t["kernel_n"]["mips_topk"]
    sweep = work.mips_topk(0, c["catalog"], d, k)
    row = work.mips_topk(1, c["catalog"], d, k)
    per_row = (row[0] - sweep[0], row[1] - sweep[1])
    works = [sweep] * calls + [per_row] * c["served"]
    return readers.roofline(r, ["mips_topk"], works, 1)
