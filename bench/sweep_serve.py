#!/usr/bin/env python3
"""Find a serve cell's knee: the highest open-loop rate its server
sustains. Runs once, by hand, when a serve cell is defined; the cell's
traffic file then fixes its rate at about four fifths of the knee.

    python3 bench/sweep_serve.py --workload <cell> --rates 1000,2000,4000 \
        [--seconds 10] [--seed 1]

One server (set up once) takes each rate's open-loop window in turn;
one JSON line per rate: offered and completed requests per second, the
95th and 50th percentile latency from the due time, failed requests and
the generator's lag. The knee is the highest rate whose completed rate
keeps up with the offered one, with no failures and a p95 that does not
grow with the window.

``--keep-handles`` keeps every request's handle alive until the window's
end, as a client in the server's own process that held on to them
would; it shows what the collector's full passes over them cost.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # as bench/run.py


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--keep-handles", action="store_true")
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import numpy as np

    from harness import cell, traffic
    from kinds import serve
    from run import chips_for, configure_jax

    spec = cell.load(ROOT, args.workload)
    chips_for(spec)
    configure_jax(spec)
    server, _ = serve.build_server(spec.config, spec.traffic, args.seed)
    kept = []
    if args.keep_handles:
        submit = server.submit
        server.submit = lambda h: kept.append(submit(h)) or kept[-1]
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(spec.traffic, rate_per_s=rate)
            due = traffic.arrivals(mix, args.seconds, args.seed)
            hist, _ = serve.histories(spec.config, len(due), args.seed)
            serve.warm_up(server, hist)
            cell.quiesce()
            before = (server.degraded_served, server.rejected)
            t0 = time.monotonic()
            w = serve.open_loop(server, due, hist, args.seconds)
            done = time.monotonic() - t0
            kept.clear()
            print(json.dumps({
                "rate_per_s": rate, "requests": len(due),
                "completed_per_s": int(w.answered.sum()) / done,
                "p95_ms": serve.p95(w.lat),
                "p50_ms": float(np.median(w.lat)),
                "p99_ms": float(np.percentile(w.lat, 99)),
                "failed": int((~w.answered).sum()),
                "degraded": server.degraded_served - before[0],
                "rejected": server.rejected - before[1],
                "lag_p95_ms": 1e3 * float(np.percentile(w.lag, 95)),
                "lag_max_ms": 1e3 * float(np.max(w.lag)),
                "gc_pause_max_ms": max(w.gc_pauses_ms, default=0.0),
                "gc_full_passes": len(w.gc_pauses_ms),
                "kept_handles": args.keep_handles,
                "recompiles": server.cache_misses}), flush=True)
    finally:
        server.close()


if __name__ == "__main__":
    main()
