"""One run of one cell: find its files by name, drive its kind, read
its metrics and assemble the result line.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
and a traffic mix; both are found by name:

* ``configs/<config>.json`` — the sizes as they are run;
* ``traffic/<traffic>.json`` — the mix's parameters; its ``kind`` names
  the driver ``kinds/<kind>.py``, which runs the system under test;
* ``limits/<workload>.json`` — the limit of each number the correctness
  check compares;
* ``metrics/<metric>.py`` — one reader per per-layer metric.

A new cell or metric is new files and an entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import tempfile
import time
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Spec:
    workload: Dict
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load(root: str, workload: str) -> Spec:
    """The files of ``workload`` under the benchmark at ``root``."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"({sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return Spec(
        workload=w,
        config=_read_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=_read_json(os.path.join(BENCH, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(BENCH, "limits", workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


def _reader(name: str) -> Callable:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a kind driver hands back."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: Dict[str, float]  # compared numbers, by name
    counters: Dict  # host-side counts for the per-layer readers
    memory_peak_bytes: int
    trace: Optional[Dict] = None  # trace.reduce_events of the window
    notes: Dict = dataclasses.field(default_factory=dict)  # for stderr


@dataclasses.dataclass
class Context:
    """What a kind driver is given."""

    spec: Spec
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: Dict
    t_process: float  # time.monotonic() at process start
    phases: Dict = dataclasses.field(default_factory=dict)

    def mark(self, phase: str) -> None:
        """Note that set-up reached the end of ``phase`` (seconds since
        the process started), for the run's notes."""
        self.phases[phase] = round(time.monotonic() - self.t_process, 3)

    def setup_s(self) -> float:
        self.mark("setup")
        return time.monotonic() - self.t_process


@contextlib.contextmanager
def traced_window(on: bool, kernels=()):
    """Trace the enclosed window when ``on``; afterwards ``box["trace"]``
    holds its reduction (see ``trace.reduce_events``), else ``None``."""
    box: Dict = {"trace": None}
    if not on:
        yield box
        return
    import jax

    from harness import trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        t0 = time.monotonic()
        try:
            yield box
        finally:
            window = time.monotonic() - t0
            jax.profiler.stop_trace()
        box["trace"] = trace.reduce_events(
            trace.read_xplane(trace.find_xplane(d)), window, kernels)


def quiesce() -> None:
    """Collect set-up's garbage and exempt what survives from later
    collections, so that a window's collector pauses scan only what the
    window allocates."""
    gc.collect()
    gc.freeze()


@contextlib.contextmanager
def gc_pauses(out: List[float]):
    """Append the length (ms) of each full collection of the garbage
    collector inside the enclosed block to ``out``."""
    start: List[float] = []

    def note(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            start[:] = [time.perf_counter()]
        elif start:
            out.append(1e3 * (time.perf_counter() - start[0]))

    gc.callbacks.append(note)
    try:
        yield out
    finally:
        gc.callbacks.remove(note)


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def judge(checks: Dict[str, float], limits: Dict) -> (bool, Dict):
    """Each compared number beside its limit; correct when every number
    is finite and at most its limit, and every limit has its number."""
    shown = {}
    ok = True
    for name, lim in limits.items():
        value = checks.get(name, math.nan)
        shown[name] = {"value": value, "limit": lim["limit"]}
        ok &= math.isfinite(value) and value <= lim["limit"]
    return ok, shown


def run(spec: Spec, *, seed: int, seconds: float, trace: bool, devices,
        peaks: Dict, t_process: float):
    """Drive the cell once; returns the result object and the driver's
    notes on the comparison (where each compared number is worst)."""
    kind = importlib.import_module("kinds." + spec.traffic["kind"])
    ctx = Context(spec, seed, seconds, trace, devices, peaks, t_process)
    ctx.mark("imports")
    out: Run = kind.run(ctx)
    ok, shown = judge(out.checks, spec.limits)
    if trace:
        readings = {"counters": out.counters, "trace": out.trace,
                    "peaks": peaks, "config": spec.config,
                    "traffic": spec.traffic}
        metrics = {}
        for m in spec.per_layer:
            value = _reader(m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]],
                               "unit": m["unit"]} for m in spec.end_to_end}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": bool(ok), "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    if trace and out.trace is not None:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
        result["breakdown"] = {"device_ops": out.trace["device_ops"],
                               "idle_gaps": out.trace["idle_gaps"]}
    result["checks"] = shown  # last key: each compared number, its limit
    return result, dict(out.notes, setup_phases=ctx.phases)
