#!/usr/bin/env python3
"""The control of a cell's correctness check: the reference put in the
program's place, one precision step below the configuration's
(``high``, three bfloat16 passes, for float32 at ``highest``), compared
with the reference by the cell's own numbers on each seed.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--mode high]

``--mode`` ``bf16`` reads one step further down; for a train cell
``half_batch`` reads that planted fault (the reference at ``highest``
with half of each batch left out) at the cell's own size.

One JSON line per seed: the compared numbers and whether each passes
its limit. The control has to fail at least one of them. The benchmark's
own runs never run this; ``tests/test_bench.py`` runs it at a small size.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # as bench/run.py


def readings(spec, seed: int, mode: str, seconds: float):
    """The cell's compared numbers for the control at ``seed``, and the
    notes on them."""
    import importlib

    kind = importlib.import_module("kinds." + spec.traffic["kind"])
    return kind.control_numbers(spec.config, spec.traffic, seed, mode,
                                seconds)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mode", default="high")
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax

    from harness import cell

    spec = cell.load(ROOT, args.workload)
    jax.config.update("jax_default_matmul_precision",
                      spec.config["matmul_precision"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        nums, notes = readings(spec, seed, args.mode, seconds)
        ok, shown = cell.judge(nums, spec.limits)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "mode": args.mode, "passes": ok,
                          "numbers": shown, "notes": notes}), flush=True)


if __name__ == "__main__":
    main()
