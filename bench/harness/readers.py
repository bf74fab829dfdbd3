"""Arithmetic shared by the per-layer readers in ``metrics/``. Each
reader returns ``None`` where the run gives it nothing to read (no
trace, or no event of its kernel), never 0."""
from __future__ import annotations

from typing import Iterable, Optional, Sequence

from harness import work


def idle_share(r) -> Optional[float]:
    """Per cent of the traced window in which no operation ran on the
    device."""
    t = r["trace"]
    if not t or not t["n_devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline(r, kernels: Sequence[str], works: Iterable[work.Work],
             calls: int) -> Optional[float]:
    """Per cent of the kernels' summed device time that the least time
    of ``calls`` × ``works`` would take at the chip's peaks."""
    t = r["trace"]
    if not t:
        return None
    spent = sum(t["kernel_s"].get(k, 0.0) for k in kernels)
    if spent <= 0.0:
        return None
    return 100.0 * calls * work.least_seconds(works, r["peaks"]) / spent


def utilization(r, flops_per_unit: float, units: float) -> Optional[float]:
    """Per cent of the chip's bf16 peak that ``units`` × ``flops_per_unit``
    over the counted window would use."""
    c = r["counters"]
    if not units or not c.get("window_s"):
        return None
    return (100.0 * flops_per_unit * units / c["window_s"]
            / r["peaks"]["bf16_flops_per_s"])
