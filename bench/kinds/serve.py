"""Serve cells: open-loop retrieval requests into the program's
``launch.serve.RetrievalServer``.

Set-up builds the server for the configuration (its AOT bucket programs
compile or load from the cache, and its readiness gate runs), hands it
the weights the benchmark makes from the seed, and warms every bucket
through the synchronous and the asynchronous paths. Each request is one
held-out user's history from the configuration's own data generator
(the eval split of the stream the train cell feeds). The window submits
each request at its due time from one thread, whatever the server's
state (an open loop), for ``--seconds``; each request is timed from its
due time to the moment the server finished it. The generator keeps a
request's handle only until it is answered, as a client in another
process would: what it keeps of an answer goes into preallocated
arrays, so that its bookkeeping adds no work to the process's garbage
collector. Rejected, degraded and unfinished requests count as failed,
and as missing every latency limit. Afterwards a sample of the finished
requests, drawn from the seed and holding the longest histories, is
compared with the reference's top-k.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import math
import time
from typing import Dict

import numpy as np

from harness import cell, checks, seeds, traffic
from kinds import train

KERNELS = ("mips_topk",)
WAIT_AFTER_S = 60.0  # how long past the window a late answer is awaited
USERS_PER_DRAW = 1024  # held-out users the data generator makes at once


def failed_ms(seconds: float) -> float:
    """The latency a failed request reads: the window and the wait past
    it, so that it misses any latency limit."""
    return 1e3 * (seconds + WAIT_AFTER_S)


def histories(config, n: int, seed: int):
    """``n`` request histories, ``(n, max_len)`` int32 right-aligned, and
    their lengths: held-out users of the configuration's data generator,
    drawn from the seed."""
    from repro.data import Cursor

    data = train.dataset(config, USERS_PER_DRAW)
    draws = -(-n // USERS_PER_DRAW)
    hist = np.concatenate([
        data.eval_batch(Cursor(seed=seed, step=i))[0]["tokens"]
        for i in range(draws)])[:n].astype(np.int32)
    return hist, (hist != 0).sum(axis=1)


def build_server(config, mix, seed: int):
    """The server at the configuration's sizes with the seeded weights
    (made on the device by the reference's initializer)."""
    import jax

    from reference import sasrec as ref
    from repro.launch.serve import RetrievalServer

    server = RetrievalServer(
        config["arch"], buckets=tuple(mix["buckets"]), top_k=mix["top_k"],
        queue_size=mix["queue_size"], shape=mix["shape"], seed=seed)
    sizes = config["sizes"]
    have = {k: getattr(server.cfg, k) for k in
            ("n_items", "max_len", "d_model", "n_layers", "n_heads",
             "d_ff_actual", "row_pad_multiple")}
    want = dict(sizes, d_ff_actual=sizes["d_ff"])
    if any(have[k] != want[k] for k in have):
        server.close()
        raise ValueError(f"server sizes {have} are not the stated {sizes}")
    k_init = jax.random.split(seeds.key(seed))[0]
    server.params = jax.jit(functools.partial(ref.init_params,
                                              sizes=sizes))(k_init)
    gc.collect()
    if not server.ready:
        server.close()
        raise RuntimeError(f"server not ready: {server.readiness_error}")
    return server, k_init


def warm_up(server, hist: np.ndarray) -> None:
    """Every bucket once synchronously, then a burst and a trickle
    through the queue."""
    for b in server.router.buckets:
        server.score(hist[:b])
    for r in [server.submit(h) for h in hist[:2 * server.router.max_bucket]]:
        r.result(timeout=600.0)
    for h in hist[:4]:
        server.submit(h).result(timeout=600.0)


@dataclasses.dataclass
class Window:
    """What an open-loop window leaves: per request its latency (ms; a
    failed request reads ``failed_ms``, later than any answer the wait
    allows), whether it was answered in full, its answer, and the lag
    of its submission behind its due time; the window's length and the
    collector's pauses in it."""

    lat: np.ndarray
    answered: np.ndarray
    ids: np.ndarray
    vals: np.ndarray
    lag: np.ndarray
    window_s: float
    gc_pauses_ms: list


def open_loop(server, due: np.ndarray, hist: np.ndarray,
              seconds: float) -> Window:
    """Submit request ``i`` at ``due[i]`` seconds into the window, and
    wait for the answers."""
    import jax

    from repro.launch.serve import ServerOverloadedError

    n, k = len(due), server.top_k
    w = Window(lat=np.full(n, failed_ms(seconds)),
               answered=np.zeros(n, bool), ids=np.zeros((n, k), np.int32),
               vals=np.zeros((n, k), np.float32), lag=np.zeros(n),
               window_s=seconds, gc_pauses_ms=[])
    pending = collections.deque()

    def keep(i, r, timeout):
        try:
            res = r.result(timeout=timeout)
        except (TimeoutError, ServerOverloadedError):
            return
        if not res.degraded:
            w.lat[i] = (r.t_done - (t0 + due[i])) * 1e3
            w.ids[i], w.vals[i], w.answered[i] = res.ids, res.vals, True

    with cell.gc_pauses(w.gc_pauses_ms):
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.monotonic() + 0.01
            for i in range(n):
                while pending and pending[0][1].done():
                    keep(*pending.popleft(), 0.0)
                wait = t0 + due[i] - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                w.lag[i] = time.monotonic() - (t0 + due[i])
                try:
                    pending.append((i, server.submit(hist[i])))
                except ServerOverloadedError:
                    pass
            w.window_s = max(time.monotonic() - t0, seconds)
        close = t0 + w.window_s + WAIT_AFTER_S
        while pending:
            keep(*pending.popleft(), max(0.0, close - time.monotonic()))
    return w


def p95(lat: np.ndarray) -> float:
    """95th percentile (nearest rank) of all requests' latencies."""
    s = np.sort(lat)
    return float(s[max(0, math.ceil(0.95 * len(s)) - 1)])


def sample(answered: np.ndarray, lens: np.ndarray, n: int,
           seed: int) -> np.ndarray:
    """Up to ``n`` of the requests answered in full, drawn from the seed,
    holding those with the longest history."""
    done = np.flatnonzero(answered)
    if len(done) <= n:
        return done
    longest = done[np.argsort(-lens[done], kind="stable")[: n // 8]]
    rest = np.setdiff1d(done, longest)
    pick = seeds.rng(seed, 4).choice(rest, n - len(longest), replace=False)
    return np.sort(np.concatenate([longest, pick]))


def reference_topk(k_init, sizes: Dict, hist: np.ndarray, k: int,
                   mode: str = "highest"):
    """The reference at ``mode`` for ``hist`` against the catalog's
    valid ids ``[1, n_items)``: its top-``k`` scores, each row's largest
    score magnitude, a function that reads its score of given ids, and
    its top-``k`` ids."""
    import jax
    import jax.numpy as jnp

    from reference import sasrec as ref

    with jax.default_matmul_precision("highest"):
        params = jax.jit(functools.partial(ref.init_params,
                                           sizes=sizes))(k_init)
        states = ref.forward(params, sizes, jnp.asarray(hist), mode)[:, -1]
        s = ref.scores(states, params["item_emb"][1:sizes["n_items"]], mode)
        best, top = jax.lax.top_k(s, k)
        scale = jnp.max(jnp.abs(s), axis=1)
    return (np.asarray(best), np.asarray(scale),
            lambda ids: np.asarray(jnp.take_along_axis(
                s, jnp.asarray(ids, jnp.int32) - 1, axis=1)),
            np.asarray(top) + 1)


def served_gap(ids: np.ndarray, vals, best, scale, score_of, n_items: int):
    ok = bool(np.all((ids >= 1) & (ids < n_items)))
    got = score_of(np.clip(ids, 1, n_items - 1))
    return checks.topk_gap(best, got, vals, scale, ok)


def compare(k_init, sizes, w: Window, pick: np.ndarray, hist: np.ndarray,
            top_k: int) -> float:
    """``topk_gap`` of the answers of requests ``pick``."""
    best, scale, score_of, _ = reference_topk(k_init, sizes, hist[pick],
                                              top_k)
    return served_gap(w.ids[pick], w.vals[pick], best, scale, score_of,
                      sizes["n_items"])


def run(ctx: cell.Context) -> cell.Run:
    config, mix = ctx.spec.config, ctx.spec.traffic
    sizes = config["sizes"]
    due = traffic.arrivals(mix, ctx.seconds, ctx.seed)
    hist, lens = histories(config, len(due), ctx.seed)
    ctx.mark("traffic")
    server, k_init = build_server(config, mix, ctx.seed)
    ctx.mark("server")
    try:
        warm_up(server, hist)
        cell.quiesce()
        served0 = server.served
        setup_s = ctx.setup_s()
        with cell.traced_window(ctx.trace, KERNELS) as box:
            w = open_loop(server, due, hist, ctx.seconds)
        peak = cell.memory_peak_bytes(ctx.devices)
        counters = {"requests": len(due), "served": server.served - served0,
                    "window_s": w.window_s, "catalog": sizes["n_items"],
                    "lag_p95_ms": 1e3 * float(np.percentile(w.lag, 95)),
                    "rejected": server.rejected,
                    "degraded": server.degraded_served,
                    "recompiles": server.cache_misses}
    finally:
        server.close()
    del server
    gc.collect()

    pick = sample(w.answered, lens, mix["check_requests"], ctx.seed)
    gap = compare(k_init, sizes, w, pick, hist, mix["top_k"])
    notes = {"failed_degraded_rejected": [
                 int((~w.answered).sum()), counters["degraded"],
                 counters["rejected"]],
             "lag_max_ms": 1e3 * float(w.lag.max()),
             "gc_pauses_ms": sorted(w.gc_pauses_ms)[-3:]}
    return cell.Run(
        attempted=len(due), failed=int((~w.answered).sum()),
        end_to_end={"serve_p95_ms": p95(w.lat), "setup_s": setup_s},
        checks={"topk_gap": gap}, counters=counters, notes=notes,
        memory_peak_bytes=peak, trace=box["trace"])


class Readings:
    """The compared numbers of sound runs on many seeds in one process:
    one server; per seed its weights and a short open-loop window at
    the cell's rate."""

    SECONDS = 2.0

    def __init__(self, config, mix):
        self.config, self.mix = config, mix
        self.server, _ = build_server(config, mix, 0)

    def __call__(self, seed: int):
        import jax

        from reference import sasrec as ref

        sizes, mix = self.config["sizes"], self.mix
        k_init = jax.random.split(seeds.key(seed))[0]
        self.server.params = jax.jit(functools.partial(
            ref.init_params, sizes=sizes))(k_init)
        due = traffic.arrivals(mix, self.SECONDS, seed)
        hist, lens = histories(self.config, len(due), seed)
        warm_up(self.server, hist)
        cell.quiesce()
        w = open_loop(self.server, due, hist, self.SECONDS)
        pick = sample(w.answered, lens, mix["check_requests"], seed)
        return {"topk_gap": compare(k_init, sizes, w, pick, hist,
                                    mix["top_k"])}, {}


def control_numbers(config, mix, seed: int, mode: str, seconds: float):
    """``topk_gap`` with the reference at ``mode`` serving the requests
    a run's check samples (the longest and a seeded draw)."""
    import jax

    sizes = config["sizes"]
    due = traffic.arrivals(mix, seconds, seed)
    hist, lens = histories(config, len(due), seed)
    pick = sample(np.ones(len(due), bool), lens, mix["check_requests"], seed)
    k_init = jax.random.split(seeds.key(seed))[0]
    best, scale, score_of, _ = reference_topk(k_init, sizes, hist[pick],
                                              mix["top_k"])
    lo_best, _, _, lo_ids = reference_topk(
        k_init, sizes, hist[pick], mix["top_k"], mode)
    return {"topk_gap": served_gap(lo_ids, lo_best, best, scale, score_of,
                                   sizes["n_items"])}, {}
