"""Profiler trace → device busy time, kernel times and idle gaps.

``read_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into a
flat list of :class:`Event` (device operations, and the benchmark's own
``bench.*`` host spans); :func:`reduce_events` is pure arithmetic on
that list, so ``tests/test_bench.py`` checks it on a small recorded
trace.

* busy time: the union of the intervals of the device's operations
  (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), averaged over
  the devices;
* a kernel's time: the summed durations of the operations whose HLO
  instruction name contains the kernel's name (the Pallas ``name=``
  of the kernel: ``mips_topk.3`` for ``mips_topk``). On the TPU an
  operation's event is named by its whole HLO text,
  ``%mips_topk.3 = (f32[...]) custom-call(... %operand ...)``; only the
  instruction's own name counts, never its operands';
* idle gaps: the holes in the busy union, each labelled with the
  innermost ``bench.*`` host span that covers its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OP_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    kind: str  # "op" (device operation) or "span" (benchmark host span)
    device: str  # plane name of the device, "" for host spans
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_xplane(path: str) -> List[Event]:
    """Device operations of every TPU plane and the ``bench.*`` host
    spans, from one ``.xplane.pb``. Raises if a TPU plane has no
    ``XLA Ops`` line, or if there is no TPU plane at all."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events: List[Event] = []
    n_devices = 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if OP_LINE not in lines:
                raise ValueError(
                    f"{plane.name} has no {OP_LINE!r} line: {sorted(lines)}")
            n_devices += 1
            for e in lines[OP_LINE].events:
                events.append(Event("op", plane.name, e.name,
                                    float(e.start_ns), float(e.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        events.append(Event("span", "", e.name,
                                            float(e.start_ns),
                                            float(e.duration_ns)))
    if not n_devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane in {path}")
    return events


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _span_at(spans: Sequence[Event], starts: Sequence[float],
             outer: Sequence[Event], t: float) -> str:
    """Innermost (latest-starting) host span covering time ``t``: among
    the 64 spans that start last before ``t``, else among ``outer``."""
    i = bisect.bisect_right(starts, t)
    for s in reversed(spans[max(0, i - 64):i]):
        if s.end_ns >= t:
            return s.name
    for s in reversed(outer):
        if s.start_ns <= t <= s.end_ns:
            return s.name
    return "outside bench spans"


_SUFFIX = re.compile(r"(\.\d+)+$")


def instruction(text: str) -> str:
    """The HLO instruction's own name:
    ``%mips_topk.3 = (f32[...]) custom-call(...)`` → ``mips_topk.3``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def op_name(text: str) -> str:
    """The instruction's name without its numeric suffix (``mips_topk``)."""
    return _SUFFIX.sub("", instruction(text))


def reduce_events(events: Sequence[Event], window_s: float,
                  kernels: Sequence[str] = ()) -> Dict:
    """Busy seconds (mean over devices), per-kernel device seconds and
    event counts (summed over devices), the top device operations by time and the
    longest idle gaps labelled by host span.

    ``window_s`` is the traced window's length on the host clock.
    """
    ops = [e for e in events if e.kind == "op"]
    spans = sorted((e for e in events if e.kind == "span"),
                   key=lambda e: e.start_ns)
    starts = [s.start_ns for s in spans]
    longest = max((s.dur_ns for s in spans), default=0.0)
    outer = [s for s in spans if s.dur_ns >= 0.1 * longest]
    by_device: Dict[str, List[Event]] = collections.defaultdict(list)
    for e in ops:
        by_device[e.device].append(e)
    if not by_device:
        return {"busy_s": 0.0, "window_s": window_s, "kernel_s": {},
                "kernel_n": {}, "device_ops": [], "idle_gaps": [], "n_devices": 0}

    busy, gaps = [], []
    for evs in by_device.values():
        merged = _union((e.start_ns, e.end_ns) for e in evs)
        busy.append(sum(e - s for s, e in merged))
        for (_, end), (nxt, _) in zip(merged, merged[1:]):
            mid = (end + nxt) / 2
            gaps.append((_span_at(spans, starts, outer, mid), (nxt - end) * 1e-9))

    kernel_s = {k: 0.0 for k in kernels}
    kernel_n = {k: 0 for k in kernels}
    per_op: Dict[str, float] = collections.defaultdict(float)
    for e in ops:
        per_op[instruction(e.name)] += e.dur_ns * 1e-9
        name = op_name(e.name)
        for k in kernels:
            if k in name:
                kernel_s[k] += e.dur_ns * 1e-9
                kernel_n[k] += 1
    n_dev = len(by_device)
    return {
        "busy_s": sum(busy) * 1e-9 / n_dev,
        "window_s": window_s,
        "kernel_s": kernel_s,
        "kernel_n": kernel_n,
        "device_ops": sorted(([k, v] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": [list(g) for g in
                      sorted(gaps, key=lambda g: -g[1])[:TOP]],
        "n_devices": n_dev,
    }
