"""Model FLOPs of the LM train step (``work_lm.lm_train_step_flops``,
with the window's mean assignments to held experts a step) × steps per
second of the traced window ÷ the chip's bf16 peak."""
from harness import readers, work_lm


def read(r):
    c = r["counters"]
    if not c.get("steps"):
        return None
    flops = work_lm.lm_train_step_flops(
        r["config"], c["tokens"], c["routed"] / c["steps"], c["n_b"],
        c["b_x"], c["b_y"])
    return readers.utilization(r, flops, c["steps"])
