"""Model FLOPs of the SCE train step (``work.sce_train_step_flops``) ×
steps per second of the traced window ÷ the chip's bf16 peak."""
from harness import readers, work


def read(r):
    c = r["counters"]
    flops = work.sce_train_step_flops(r["config"]["sizes"], c["positions"],
                                      c["catalog"], c["n_b"], c["b_x"],
                                      c["b_y"])
    return readers.utilization(r, flops, c["steps"])
