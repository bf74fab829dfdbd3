"""Eval cells: the program's full-catalog leave-one-out evaluation,
``eval.harness.evaluate_streaming``, call after call.

Set-up makes the weights on the device from the seed (the reference's
initializer, one jitted call) and a held-out set of ``batches`` ×
``users_per_call`` users from the program's data generator
(``eval_batch``, the held-out split), then evaluates the first batch
twice. The window evaluates the batches in turn for ``--seconds``;
each call returns its HR/NDCG/COV, computed from per-user ranks and
top-k ids that a recording accumulator keeps. Afterwards one call drawn
from the seed is compared with the reference: every user's rank
(``checks.rank_gap``) and top-k ids (``checks.topk_gap``), as one
number, ``score_gap``.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from harness import cell, checks, seeds
from kinds import train

KERNELS = ("eval_fused", "eval_tgt_gather")
BLOCK = 128  # users per block of the reference's full-catalog scores


def held_out(config, mix, seed: int):
    from repro.data import Cursor

    data = train.dataset(config, mix["users_per_call"])
    return [data.eval_batch(Cursor(seed=seed, step=i))[0]["tokens"]
            for i in range(mix["batches"])]


def leave_one_out(tokens: np.ndarray):
    """Users with at least two items; their last item is the target and
    the rest, shifted right by one, the input."""
    kept = tokens[(tokens != 0).sum(axis=1) >= 2]
    target = kept[:, -1].copy()
    prefix = np.zeros_like(kept)
    prefix[:, 1:] = kept[:, :-1]
    return prefix, target


def reference_ranks(k_init, sizes, tokens, ranks=None, ids=None, *,
                    k: int = 10, mode: str = "highest"):
    """``score_gap``, the wider of ``checks.rank_gap`` and
    ``checks.topk_gap``, of the per-user ``ranks`` and top-k ``ids`` of
    one held-out batch against the reference, and both gaps. Without
    them, the ranks and top-``k`` of the reference at ``mode`` are
    judged."""
    import jax
    import jax.numpy as jnp

    from reference import sasrec as ref

    n = sizes["n_items"]
    prefix, target = leave_one_out(tokens)

    def scored(params, pre, tgt, m):
        s = ref.scores(ref.forward(params, sizes, pre, m)[:, -1],
                       params["item_emb"][1:n], m)
        return s, s[jnp.arange(s.shape[0]), tgt - 1]

    @jax.jit
    def own(params, pre, tgt):  # the reference's answers at ``mode``
        s, s_t = scored(params, pre, tgt, mode)
        r = (jnp.sum(s > s_t[:, None], axis=1)
             + jnp.maximum(jnp.sum(s == s_t[:, None], axis=1) - 1, 0))
        return r, jax.lax.top_k(s, k)[1] + 1

    @jax.jit
    def judged(params, pre, tgt, r, top):
        s, s_t = scored(params, pre, tgt, "highest")
        desc = -jnp.sort(-s, axis=1)
        at = desc[jnp.arange(s.shape[0]), jnp.clip(r, 0, n - 2)]
        got = jnp.take_along_axis(s, jnp.clip(top, 1, n - 1) - 1, axis=1)
        ok = jnp.all((top >= 1) & (top < n))
        return (at, s_t, jnp.max(jnp.abs(s), axis=1),
                jax.lax.top_k(s, top.shape[1])[0], got, ok)

    with jax.default_matmul_precision("highest"):
        params = jax.jit(functools.partial(ref.init_params,
                                           sizes=sizes))(k_init)
        parts = []
        for a in range(0, len(target), BLOCK):
            pre = jnp.asarray(prefix[a:a + BLOCK])
            tgt = jnp.asarray(target[a:a + BLOCK])
            if ranks is None:
                r, top = own(params, pre, tgt)
            else:
                r = jnp.asarray(ranks[a:a + BLOCK])
                top = jnp.asarray(ids[a:a + BLOCK, :k])
            parts.append([np.asarray(v) for v in judged(params, pre, tgt,
                                                        r, top)])
    at, s_t, scale, best, got = (np.concatenate([p[i] for p in parts])
                                 for i in range(5))
    ok = all(bool(p[5]) for p in parts)
    gaps = {"rank_gap": checks.rank_gap(at, s_t, scale),
            "topk_gap": checks.topk_gap(best, got, None, scale, ok)}
    # One number: with random weights the top-k of 10^6 items lies far
    # apart in score, so the top-k gap alone reads 0 for the program and
    # on most seeds for the control too; the rank gap separates them.
    return {"score_gap": max(gaps.values())}, gaps


def _recording(ks, n_items):
    """A ``MetricAccumulator`` that keeps the per-user ranks and top-k
    ids the program folds into it."""
    from repro.eval.streaming import MetricAccumulator

    class Recording(MetricAccumulator):
        def update(self, ranks, topk_ids):
            self.ranks, self.ids = np.asarray(ranks), np.asarray(topk_ids)
            super().update(ranks, topk_ids)

    return Recording(ks, n_items)


def _weights(sizes, seed: int):
    import jax

    from reference import sasrec as ref

    k_init = jax.random.split(seeds.key(seed))[0]
    return k_init, jax.jit(functools.partial(ref.init_params,
                                             sizes=sizes))(k_init)


def run(ctx: cell.Context) -> cell.Run:
    import jax

    from repro.eval import evaluate_streaming

    config, mix = ctx.spec.config, ctx.spec.traffic
    sizes = config["sizes"]
    ks = tuple(mix["ks"])
    cfg = train.model_config(config)
    k_init, params = _weights(sizes, ctx.seed)
    batches = held_out(config, mix, ctx.seed)
    ctx.mark("weights_and_data")
    for _ in range(2):
        evaluate_streaming(params, cfg, {"tokens": batches[0]}, ks=ks)

    cell.quiesce()
    setup_s = ctx.setup_s()
    calls, users, kept = 0, 0, []
    with cell.traced_window(ctx.trace, KERNELS) as box:
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.window"):
            while calls == 0 or time.monotonic() - t0 < ctx.seconds:
                acc = _recording(ks, cfg.n_items)
                with jax.profiler.TraceAnnotation("bench.eval_call"):
                    evaluate_streaming(
                        params, cfg, {"tokens": batches[calls % len(batches)]},
                        ks=ks, accumulator=acc)
                kept.append((acc.ranks, acc.ids))
                users += acc.n_users
                calls += 1
        window_s = time.monotonic() - t0
    peak = cell.memory_peak_bytes(ctx.devices)
    del params
    gc.collect()

    pick = int(seeds.rng(ctx.seed, 5).integers(calls))
    ranks, ids = kept[pick]
    gaps, notes = reference_ranks(k_init, sizes,
                                  batches[pick % len(batches)], ranks, ids,
                                  k=max(ks))
    return cell.Run(
        attempted=calls, failed=0,
        end_to_end={"eval_users_per_s": users / window_s, "setup_s": setup_s},
        checks=gaps, notes=notes,
        counters={"calls": calls, "users": users, "window_s": window_s,
                  "catalog": cfg.catalog_loss_size,
                  "rows": users // calls, "k": max(ks)},
        memory_peak_bytes=peak, trace=box["trace"])


class Readings:
    """The compared numbers of sound calls on many seeds in one process:
    per seed its weights and one call on its first held-out batch."""

    def __init__(self, config, mix):
        self.config, self.mix = config, mix
        self.cfg = train.model_config(config)

    def __call__(self, seed: int):
        from repro.eval import evaluate_streaming

        sizes, ks = self.config["sizes"], tuple(self.mix["ks"])
        k_init, params = _weights(sizes, seed)
        tokens = held_out(self.config, dict(self.mix, batches=1), seed)[0]
        acc = _recording(ks, self.cfg.n_items)
        evaluate_streaming(params, self.cfg, {"tokens": tokens}, ks=ks,
                           accumulator=acc)
        del params
        return reference_ranks(k_init, sizes, tokens, acc.ranks, acc.ids,
                               k=max(ks))


def control_numbers(config, mix, seed: int, mode: str, seconds: float):
    """The gaps of the reference's own ranks and top-k at ``mode`` on the
    first held-out batch."""
    import jax

    k_init = jax.random.split(seeds.key(seed))[0]
    return reference_ranks(k_init, config["sizes"],
                           held_out(config, mix, seed)[0],
                           k=max(mix["ks"]), mode=mode)
