"""Model FLOPs of the requests served in the traced window (encoder
forward over each history and one catalog-wide score row,
``work.retrieval_flops``) per second ÷ the chip's bf16 peak."""
from harness import readers, work


def read(r):
    c = r["counters"]
    per = work.retrieval_flops(r["config"]["sizes"], 1, c["catalog"])
    return readers.utilization(r, per, c["served"])
