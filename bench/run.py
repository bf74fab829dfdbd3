#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout on a machine with the cell's chips. The
cell's configuration, traffic, limits and per-layer readers are found
by name from ``BENCHMARK.json`` (see ``harness/cell.py``). The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1``
the per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``, each compared number beside its limit; the same
numbers are the last lines of standard error.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, where the device kind is not in the peaks
table, or where the program under test (``src/``) is missing.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# libtpu writes its logs under /tmp by default; a run writes only in its
# checkout and its own temporary directory.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(2)


def chips_for(spec):
    """The cell's TPU devices and their peaks row; exits (2) where JAX
    finds no TPU, fewer chips than the cell asks for, or a device kind
    the peaks table lacks."""
    import jax

    from harness import peaks

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX platform is {devices[0].platform!r}")
    chips = spec.workload["chips"]
    if len(devices) < chips:
        fail(f"the cell asks for {chips} chips, {len(devices)} present")
    try:
        return devices[:chips], peaks.peaks_for(devices[0].device_kind)
    except peaks.UnknownDevice as e:
        fail(str(e))


def configure_jax(spec) -> None:
    """The program's compile cache, keeping every program however quick
    its compile, and the configuration's matmul precision. The program's
    entry points set none, and so run XLA's default (one bfloat16 pass)
    outside their kernels; a configuration states the precision it is
    timed and checked at."""
    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      spec.config["matmul_precision"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

    from harness import cell

    spec = cell.load(ROOT, args.workload)
    devices, table = chips_for(spec)
    configure_jax(spec)
    result, notes = cell.run(spec, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), devices=devices,
                             peaks=table, t_process=T_PROCESS)
    sys.stdout.flush()
    print(f"notes: {json.dumps(notes)}", file=sys.stderr)
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
