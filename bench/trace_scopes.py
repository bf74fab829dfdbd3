#!/usr/bin/env python3
"""Run one cell as ``bench/run.py`` does, with the trace reduced by
``harness/scopes.py``: the program's named scopes and host spans.

    python3 bench/trace_scopes.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1> [--out <dir>]

A tool, never run by a benchmark run. ``harness/trace.py``'s reduction,
which ``bench/run.py`` uses, keeps only the benchmark's own ``bench.*``
spans and no scopes; this tool puts ``scopes.reduce_scoped`` in its
place for one run and prints one JSON line: ``correct``, the end-to-end
metrics (also when tracing), and with ``--trace 1`` every per-layer
reader of the cell's kind in ``metrics/`` (those with a
``BENCHMARK.json`` entry and those waiting for one), ``scope_s``,
``unscoped_s``, ``idle_by_span``, ``span_s``, ``span_n`` and the
program module the scopes were matched in. A cell whose files wait for
a ``BENCHMARK.json`` entry is found by its files' names.

With ``--out`` and ``--trace 1`` it also writes there
``<cell>.slice.json``: the events of the window's second and third
steps (train cells) with their scopes, and the slice's reduction, the
form of ``tests/trace_v5e_scoped.json``.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
TEXT = 40  # operation texts are cut to this many characters in a slice


def _read(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _slice(events, window_s, kernels):
    """The events of the second and third ``bench.data``-to-``bench.sync``
    steps, texts cut, with the reduction of that slice."""
    from harness import scopes

    data = sorted((e for e in events
                   if e.kind == "span" and e.name == "bench.data"),
                  key=lambda e: e.start_ns)
    if len(data) < 4:
        return None
    t0, t1 = data[1].start_ns, data[3].start_ns
    kept = [e for e in events
            if t0 <= e.start_ns < t1 and e.name != "bench.window"]
    kept = [scopes.Op(e.kind, e.device, e.name[:TEXT], e.start_ns, e.dur_ns,
                      e.scope) if e.kind == "op"
            else e for e in kept]
    red = scopes.reduce_scoped(kept, (t1 - t0) * 1e-9, kernels)
    return {
        "source": "two steps of a traced window of this cell on one TPU "
                  "v5e, with the program's scopes; operation texts cut "
                  f"after {TEXT} characters",
        "window_s": (t1 - t0) * 1e-9,
        "kernels": list(kernels),
        "events": [[e.kind, e.device, e.name, e.start_ns, e.dur_ns]
                   + ([e.scope] if e.kind == "op" else [])
                   for e in kept],
        "expected": {k: red[k] for k in
                     ("busy_s", "kernel_s", "kernel_n", "scope_s",
                      "unscoped_s", "idle_by_span", "span_n")},
    }


def step_op_names(trainer):
    """The module name and instruction → ``op_name`` map of a train
    cell's compiled step, as it runs (the jit cache holds it)."""
    import jax.numpy as jnp

    from harness import scopes

    st = trainer.st
    host, _ = trainer.data.next_batch(st["cursor"])
    dev = {k: trainer.place(v) for k, v in host.items()}
    dev["loss_cap"] = jnp.float32(st["guard"].loss_cap())
    text = trainer.step_fn.lower(st["params"], st["opt"], dev,
                                 st["key"]).compile().as_text()
    return scopes.op_names_from_hlo(text)


def scoped_window(found, out_dir, workload, trainers):
    """``cell.traced_window`` with ``scopes.read_scoped`` and
    ``scopes.reduce_scoped`` in place of ``trace``'s reading and
    reduction; a train cell's scopes come from its step's compiled
    text. Notes in ``found`` the program module they were matched in."""

    @contextlib.contextmanager
    def window(on, kernels=()):
        box = {"trace": None}
        if not on:
            yield box
            return
        import jax

        from harness import scopes, trace

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
            jax.profiler.start_trace(d, profiler_options=opts)
            t0 = time.monotonic()
            try:
                yield box
            finally:
                window_s = time.monotonic() - t0
                jax.profiler.stop_trace()
            path = trace.find_xplane(d)
            module, names = (step_op_names(trainers[-1]) if trainers
                             else ("", {}))
            events = scopes.read_scoped(path, module, names)
            found["module"] = module
            box["trace"] = scopes.reduce_scoped(events, window_s, kernels)
            if out_dir:
                sl = _slice(events, window_s, kernels)
                if sl:
                    with open(os.path.join(out_dir, workload + ".slice.json"),
                              "w") as f:
                        json.dump(sl, f)

    return window


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", help="directory for a two-step slice")
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

    import run as bench_run
    from harness import cell

    if args.out:
        os.makedirs(args.out, exist_ok=True)
    try:
        spec = cell.load(ROOT, args.workload)
    except KeyError:  # files that wait for an entry: <config>.<traffic>
        config, traffic = args.workload.rsplit(".", 1)
        spec = cell.Spec(
            workload={"name": args.workload, "config": config,
                      "traffic": traffic, "chips": 1},
            config=_read("configs", config + ".json"),
            traffic=_read("traffic", traffic + ".json"),
            limits=_read("limits", args.workload + ".json"),
            end_to_end=[], per_layer=[])
    devices, table = bench_run.chips_for(spec)
    bench_run.configure_jax(spec)
    found, trainers = {}, []
    cell.traced_window = scoped_window(found, args.out, args.workload,
                                       trainers)
    kind_name = spec.traffic["kind"]
    kind = importlib.import_module("kinds." + kind_name)
    if kind_name == "train":
        class Kept(kind.Trainer):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                trainers.append(self)

        kind.Trainer = Kept
    ctx = cell.Context(spec, args.seed, args.seconds, bool(args.trace),
                       devices, table, T_PROCESS)
    out = kind.run(ctx)
    ok, shown = cell.judge(out.checks, spec.limits)
    line = {"correct": bool(ok), "end_to_end": out.end_to_end,
            "memory_peak_bytes": out.memory_peak_bytes,
            "steps": out.counters.get("steps"), "checks": shown}
    if args.trace and out.trace is not None:
        readings = {"counters": out.counters, "trace": out.trace,
                    "peaks": table, "config": spec.config,
                    "traffic": spec.traffic}
        names = sorted(f[:-3] for f in os.listdir(os.path.join(HERE,
                                                               "metrics"))
                       if f.endswith("." + kind_name + ".py"))
        line["per_layer"] = {n: cell._reader(n)(readings) for n in names}
        t = out.trace
        line["trace"] = {k: t[k] for k in
                         ("busy_s", "window_s", "scope_s", "unscoped_s",
                          "idle_by_span", "span_s", "span_n", "idle_gaps",
                          "device_ops")}
        line["module"] = found.get("module")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
