#!/usr/bin/env python3
"""The compared numbers of sound runs of the program over many seeds,
for setting a cell's correctness limits (their lower readings).

    python3 bench/readings.py --workload <cell> --seeds 1,2,...,12

Drives the cell's own timed path at the cell's own sizes — the same
step, server or eval call with the same feed — but in one process and
without the measured window: per seed fresh weights, the first steps
(train), a short open-loop window at the cell's rate (serve) or one
call (eval), then the cell's comparison with the reference. One JSON
line per seed. The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # as bench/run.py


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import importlib

    from harness import cell
    from run import chips_for, configure_jax

    spec = cell.load(ROOT, args.workload)
    chips_for(spec)
    configure_jax(spec)
    kind = importlib.import_module("kinds." + spec.traffic["kind"])
    read = kind.Readings(spec.config, spec.traffic)
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers, notes = read(seed)
        ok, shown = cell.judge(numbers, spec.limits)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "passes": ok, "numbers": shown, "notes": notes}),
              flush=True)


if __name__ == "__main__":
    main()
