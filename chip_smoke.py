#!/usr/bin/env python3
"""Smoke test of sasrec-sce on TPU at the paper's full configuration.

Drives the system through the entry points a user calls, at
``configs/sasrec_sce.py``'s full config (173,511-item Gowalla catalog,
d = 64, batch 128 x 200, SCE exact mode, b_y = 256), with random
weights made from ``--seed``, and checks every result against a float32
reference computed at ``precision=HIGHEST``:

  one chip (default)
    train  ``repro.launch.train.train(shape="train_paper",
           guard_policy="strict")`` for 5 steps: finite losses; the
           compiled step calls the Mosaic kernels ``mips_topk`` and
           ``sce_gather``; the kernel SCE loss on one batch equals the
           pure-jnp (``use_kernel=False``) loss, and its loss and
           gradients equal ``sce_loss_sharded_ref``'s (see below).
    eval   ``repro.eval.evaluate_streaming`` over the full catalog for
           512 users: HR/NDCG equal the dense ``core.metrics`` ranks,
           with slack only on rows whose target score is a near-tie.
    serve  ``RetrievalServer`` (buckets 8, 32; 40 requests): no
           recompiles, top-10 ids equal a dense ``lax.top_k`` except at
           near-ties.

  ``--chips 4``
    shard  ``sce_loss_sharded`` in exact and union modes on a
           (data=1, model=4) and a (2, 2) mesh against
           ``sce_loss_sharded_ref`` (loss and gradients; gradient rows
           that a selection near-tie of the reference can reach are
           named from the reference alone and set aside), and
           ``RetrievalServer`` on a model x 4 mesh against the dense
           oracle. Only this phase runs.

The script runs in one process and fails at once unless JAX's platform
is ``tpu`` (it never falls back to the CPU or to interpret mode).
Timings and the verdict table go to earlier lines; the last line of
standard output is one JSON object naming the devices used.

Usage, from the repository root on a TPU host:
  python chip_smoke.py
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "sasrec-sce"
TRAIN_STEPS = 5
EVAL_USERS = 512
SERVE_BUCKETS = (8, 32)
SERVE_REQUESTS = 40
TOP_K = 10
# Two float32 scores closer than this (relative to the row's largest
# score) are a near-tie: their order may differ between two correct f32
# computations that sum a d = 64 dot product in a different order.
NEAR_TIE = 2e-7
LOSS_RTOL = 1e-4  # kernel vs reference SCE loss on identical inputs
# Relative Frobenius error of the SCE gradients, kernel path vs the
# XLA reference, over the rows no selection near-tie can touch. On a
# v5e, the sce_gather kernel's dX and dY sit 1.6e-5 to 2.9e-5 from
# float64 on fixed candidates (XLA at HIGHEST: ~1e-6); one-pass
# bfloat16 matmuls miss by ~2e-3.
GRAD_RTOL = 1e-4
# SCE selects, per bucket, the top b_x positions and top b_y items by a
# float32 score, and gives each position the loss of its largest bucket.
# Two correct float32 computations of a score (Mosaic vs XLA) differ by
# a few ulps, so where the reference's k-th and (k+1)-th scores lie
# within SELECT_TIE (relative to the bucket's largest |score|), or a
# position's two largest bucket losses within MAX_TIE (relative), the
# two paths may choose differently. Such buckets are found from the
# reference alone; the gradient rows they reach are left out of the
# GRAD_RTOL comparison, and every row that differs must be among them.
SELECT_TIE = 5e-7
MAX_TIE = 4e-6
# The comparison must still cover most of each gradient: at least this
# share of the rows where the reference gradient is nonzero.
COMPARED_SHARE = 0.5


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


class Phases:
    """Runs named phases, keeps going after a failure, and keeps the
    verdict table."""

    def __init__(self):
        self.rows = []

    def run(self, name, fn):
        t0 = time.perf_counter()
        try:
            detail = fn()
            ok = True
        except Exception as e:  # a phase failure is a verdict, not a crash
            traceback.print_exc()
            detail, ok = f"{type(e).__name__}: {e}", False
        dt = time.perf_counter() - t0
        self.rows.append((name, ok, dt, detail))
        print(f"[{name}] {'PASS' if ok else 'FAIL'} in {dt:.1f} s: {detail}",
              flush=True)

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r[1] for r in self.rows)

    def table(self) -> None:
        print("phase    verdict  seconds  detail")
        for name, ok, dt, detail in self.rows:
            print(f"{name:8} {'PASS' if ok else 'FAIL':8} {dt:7.1f}  {detail}")


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------
def _highest():
    import jax

    return jax.default_matmul_precision("highest")


def _full_setup(seed):
    """sasrec-sce's full config, its train_paper data, random params."""
    import jax

    from repro.configs import get_arch
    from repro.launch.train import _init_params, _setup

    arch = get_arch(ARCH)
    cfg, shape, data = _setup(arch, 0, 0, "train_paper")
    params = _init_params(arch, cfg, jax.random.PRNGKey(seed))
    return arch, cfg, shape, data, params


def _custom_calls(hlo: str):
    """Names of the Mosaic kernels called in a compiled HLO text."""
    return [
        line.split("=", 1)[0].strip()
        for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


def _require_kernels(hlo: str, names):
    calls = _custom_calls(hlo)
    missing = [n for n in names if not any(n in c for c in calls)]
    if missing:
        raise AssertionError(
            f"compiled program lacks Mosaic kernels {missing}; "
            f"tpu_custom_calls: {calls}"
        )
    return len(calls)


def _dense_topk(states, catalog, k):
    """HIGHEST-precision dense oracle over ids [1, C): (scores, vals, ids)."""
    import jax
    import jax.numpy as jnp

    with _highest():
        scores = states @ catalog.T
        scores = scores.at[:, 0].set(-jnp.inf)
        vals, ids = jax.lax.top_k(scores, k)
    return scores, vals, ids


def _check_topk(got_ids, got_vals, scores, want_vals, want_ids):
    """Top-k ids equal the dense oracle's except where the swapped
    items are near-ties; returns the number of near-tie swaps."""
    import numpy as np

    scores = np.asarray(scores)
    want_ids, want_vals = np.asarray(want_ids), np.asarray(want_vals)
    swaps = 0
    for r in range(len(got_ids)):
        if np.array_equal(got_ids[r], want_ids[r]):
            continue
        band = NEAR_TIE * float(np.max(np.abs(want_vals[r]))) + 1e-30
        for j in np.nonzero(got_ids[r] != want_ids[r])[0]:
            oracle_of_got = scores[r, got_ids[r][j]]
            if abs(oracle_of_got - want_vals[r, j]) > band:
                raise AssertionError(
                    f"row {r} slot {j}: id {got_ids[r][j]} (oracle score "
                    f"{oracle_of_got:.9g}) where the oracle has "
                    f"{want_ids[r, j]} ({want_vals[r, j]:.9g})"
                )
            swaps += 1
        if np.max(np.abs(got_vals[r] - want_vals[r])) > band:
            raise AssertionError(f"row {r}: top-k values differ beyond "
                                 f"the near-tie band")
    return swaps


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------
def phase_train(seed):
    import jax
    import jax.numpy as jnp

    from jax.sharding import NamedSharding

    from repro.core.distributed_sce import sce_loss_sharded
    from repro.data import Cursor
    from repro.dist.sharding import batch_spec, replicated_sharding
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train
    from repro.models import sasrec

    t0 = time.perf_counter()
    out = train(ARCH, steps=TRAIN_STEPS, shape="train_paper",
                guard_policy="strict", seed=seed, log_every=1)
    t_train = time.perf_counter() - t0
    losses = out["losses"]
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        raise AssertionError(f"losses {losses}")

    arch, cfg, shape, data, params = _full_setup(seed)
    batch = shape.dims["batch"]
    mesh = make_host_mesh(max_data=batch)
    step, (opt_init, _), sce_cfg = steps_lib.make_seqrec_train_step(
        arch, cfg, mesh, shape, sce_mode="exact"
    )
    host, _ = data.next_batch(Cursor(seed=seed))
    dev = {k: jnp.asarray(v) for k, v in host.items()}
    # The shapes and shardings train() gives the step, so this compile
    # is the program that ran (a persistent-cache hit where one is on).
    rep, rows = replicated_sharding(mesh), lambda a: NamedSharding(
        mesh, batch_spec(mesh, a.ndim))
    sds = lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    abstract = (
        jax.tree.map(lambda a: sds(a, rep), (params, opt_init(params))),
        {**{k: sds(v, rows(v)) for k, v in dev.items()},
         "loss_cap": jax.ShapeDtypeStruct((), jnp.float32)},
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    abstract = (*abstract[0], *abstract[1:])
    with jax.set_mesh(mesh):
        hlo = jax.jit(step, donate_argnums=(0, 1)).lower(
            *abstract
        ).compile().as_text()
    n_calls = _require_kernels(hlo, ("mips_topk", "sce_gather"))

    # Step 0's SCE loss through the kernels vs the pure-jnp path, on the
    # same HIGHEST-precision model outputs and the train loop's step key.
    _, step_key = jax.random.split(jax.random.PRNGKey(seed))
    k_loss = jax.random.split(step_key, 3)[1]
    ref_cfg = dataclasses.replace(sce_cfg, use_kernel=False)

    def sce(c):
        def f(p):
            x = sasrec.forward(p, cfg, dev["tokens"])
            x = x.reshape(-1, x.shape[-1])
            return sce_loss_sharded(
                x, sasrec.loss_catalog(p, cfg), dev["targets"].reshape(-1),
                key=k_loss, cfg=c, mesh=mesh,
                valid_mask=dev["valid"].reshape(-1), mode="exact",
            )
        return jax.jit(f)

    with _highest(), jax.set_mesh(mesh):
        l_kernel = float(sce(sce_cfg)(params))
        l_ref = float(sce(ref_cfg)(params))
    rel = abs(l_kernel - l_ref) / abs(l_ref)
    if not rel <= LOSS_RTOL:
        raise AssertionError(
            f"kernel SCE loss {l_kernel!r} vs jnp {l_ref!r} (rel {rel:.2e})"
        )
    with _highest():
        x = sasrec.forward(params, cfg, dev["tokens"])
    grads = _sce_vs_ref(
        x.reshape(-1, x.shape[-1]), sasrec.loss_catalog(params, cfg),
        dev["targets"].reshape(-1), dev["valid"].reshape(-1), k_loss,
        sce_cfg, mesh, "exact",
    )
    return (
        f"{TRAIN_STEPS} steps at batch {batch} x {cfg.max_len}, "
        f"C={cfg.n_items}: losses {[round(v, 5) for v in losses]}, "
        f"mean step {out['mean_step_s']:.3f} s (first step includes "
        f"compile; loop {t_train:.1f} s); {n_calls} Mosaic calls in the "
        f"step; step-0 kernel loss {l_kernel:.7f} vs jnp {l_ref:.7f} "
        f"(rel {rel:.1e}); train loop step-0 loss {losses[0]:.7f}; "
        f"grads vs reference: {grads}"
    )


def phase_eval(seed):
    import jax.numpy as jnp
    import numpy as np

    from repro.data import Cursor, SeqDataConfig, SequenceDataset
    from repro.eval import evaluate_streaming
    from repro.eval.harness import _keep_and_targets, sasrec_score_fn
    from repro.eval.streaming import ranks_from_counts, streaming_eval_scores
    from repro.models import sasrec

    _, cfg, _, _, params = _full_setup(seed)
    ks = (1, 5, 10)
    eval_batch, _ = SequenceDataset(SeqDataConfig(
        n_items=cfg.n_items, seq_len=cfg.max_len, batch_size=EVAL_USERS,
    )).eval_batch(Cursor(seed=seed))
    with _highest():
        t0 = time.perf_counter()
        got = evaluate_streaming(params, cfg, eval_batch, ks=ks)
        t_eval = time.perf_counter() - t0
        tokens, targets = _keep_and_targets(np.asarray(eval_batch["tokens"]))
        states, catalog = sasrec_score_fn(cfg)(params, jnp.asarray(tokens))
        # the scorer under the harness, for per-row ranks and top-k ids
        vals, ids, gt, eq = streaming_eval_scores(
            states, catalog, jnp.asarray(targets), max(ks),
            c_lo=1, c_hi=cfg.n_items,
        )[:4]
        scores, want_v, want_i = _dense_topk(
            states, sasrec.item_embeddings(params, cfg), max(ks)
        )
    ranks = np.asarray(ranks_from_counts(gt, eq))
    swaps = _check_topk(np.asarray(ids), np.asarray(vals), scores,
                        want_v, want_i)
    scores = np.asarray(scores)
    s_t = scores[np.arange(len(targets)), targets]
    band = NEAR_TIE * np.max(np.abs(np.where(np.isinf(scores), 0, scores)),
                             axis=1)
    # Rank bounds: exact where no other item is within the near-tie band
    # of the target, otherwise anywhere inside the band.
    lo = np.sum(scores > (s_t + band)[:, None], axis=1)
    hi = np.sum(scores >= (s_t - band)[:, None], axis=1) - 1
    near = int(np.sum(lo != hi))
    bad = np.nonzero((ranks < lo) | (ranks > hi))[0]
    if len(bad):
        r = bad[0]
        raise AssertionError(
            f"{len(bad)} rows ranked outside the oracle's bounds, e.g. row "
            f"{r}: rank {ranks[r]} vs [{lo[r]}, {hi[r]}]"
        )
    for k in ks:
        for name, f in (("hr", lambda r: (r < k).astype(float)),
                        ("ndcg", lambda r: np.where(
                            r < k, 1.0 / np.log2(r + 2.0), 0.0))):
            g = got[f"{name}@{k}"]
            lo_m, hi_m = float(np.mean(f(hi))), float(np.mean(f(lo)))
            if not lo_m - 1e-12 <= g <= hi_m + 1e-12:
                raise AssertionError(
                    f"{name}@{k} = {g!r} outside the oracle's "
                    f"[{lo_m!r}, {hi_m!r}]"
                )
    shown = {k: round(v, 5) for k, v in got.items() if not k.startswith("cov")}
    return (f"{len(targets)} users x {cfg.n_items} items in {t_eval:.2f} s "
            f"(first call includes compile): {shown}; ranks within the "
            f"oracle's bounds ({near} near-tie rows), top-{max(ks)} ids "
            f"equal ({swaps} near-tie swaps)")


def _serve_and_check(server, seed, cfg, params):
    import jax.numpy as jnp
    import numpy as np

    from repro.data import Cursor, SeqDataConfig, SequenceDataset
    from repro.models import sasrec

    tokens = SequenceDataset(SeqDataConfig(
        n_items=cfg.n_items, seq_len=cfg.max_len, batch_size=SERVE_REQUESTS,
    )).next_batch(Cursor(seed=seed + 1))[0]["tokens"]
    t0 = time.perf_counter()
    reqs = [server.submit(h) for h in tokens]
    results = [r.result(timeout=600.0) for r in reqs]
    dt = time.perf_counter() - t0
    if server.cache_misses:
        raise AssertionError(f"{server.cache_misses} recompiles")
    if any(r.degraded for r in results):
        raise AssertionError("degraded responses at this load")
    with _highest():
        hidden = sasrec.forward(params, cfg, jnp.asarray(tokens))[:, -1]
        scores, want_v, want_i = _dense_topk(
            hidden, sasrec.item_embeddings(params, cfg), TOP_K
        )
    got_i = np.stack([r.ids for r in results])
    got_v = np.stack([r.vals for r in results])
    swaps = _check_topk(got_i, got_v, scores, want_v, want_i)
    lat = sorted(r.latency_ms for r in reqs)
    return (f"{len(reqs)} requests in {dt * 1e3:.1f} ms (p50 "
            f"{lat[len(lat) // 2]:.1f} ms, max {lat[-1]:.1f} ms), "
            f"buckets {server.router.buckets}, recompiles 0, "
            f"{swaps} near-tie swaps")


def phase_serve(seed, mesh=None):
    from repro.launch.serve import RetrievalServer

    _, cfg, _, _, params = _full_setup(seed)
    with _highest():
        server = RetrievalServer(
            ARCH, buckets=SERVE_BUCKETS, top_k=TOP_K, queue_size=128,
            shape="serve_p99", seed=seed, mesh=mesh,
        )
    try:
        if not server.ready:
            raise AssertionError(f"not ready: {server.readiness_error}")
        return _serve_and_check(server, seed, cfg, params)
    finally:
        server.close()


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------
def phase_shard(seed):
    import jax
    import jax.numpy as jnp

    from repro.data import Cursor
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_sce_config
    from repro.models import sasrec

    arch, cfg, shape, data, params = _full_setup(seed)
    host, _ = data.next_batch(Cursor(seed=seed))
    with _highest():
        x = sasrec.forward(params, cfg, jnp.asarray(host["tokens"]))
        x = x.reshape(-1, x.shape[-1])
    y = sasrec.loss_catalog(params, cfg)
    t = jnp.asarray(host["targets"]).reshape(-1)
    v = jnp.asarray(host["valid"]).reshape(-1)
    key = jax.random.PRNGKey(seed + 7)
    lines = []
    for dp, tp in ((1, 4), (2, 2)):
        mesh = make_mesh((dp, tp), ("data", "model"))
        sce_cfg = build_sce_config(
            x.shape[0] // dp, cfg.n_items, bucket_size_y=arch.sce_bucket_size_y,
            tp=tp,
        )
        for mode in ("exact", "union"):
            # every mesh and mode runs, so one failure hides no other
            try:
                got = _sce_vs_ref(x, y, t, v, key, sce_cfg, mesh, mode)
                lines.append(f"({dp},{tp}) {mode}: {got}")
            except AssertionError as e:
                lines.append(f"FAIL {e}")
    if any(line.startswith("FAIL") for line in lines):
        raise AssertionError("; ".join(lines))
    return "; ".join(lines)


def _sce_vs_ref(x, y, t, v, key, sce_cfg, mesh, mode):
    """Loss and (dX, dY) of ``sce_loss_sharded`` on ``mesh`` (Mosaic
    kernels) against ``sce_loss_sharded_ref`` at HIGHEST precision.
    Inputs are placed with the mesh's batch and catalog shardings and
    must spread over every device. Raises on a mismatch; returns the
    figures as text."""
    import jax
    from jax.sharding import NamedSharding

    from repro.core.distributed_sce import sce_loss_sharded, sce_loss_sharded_ref
    from repro.dist.sharding import batch_spec, catalog_spec

    dp, tp = mesh.shape["data"], mesh.shape["model"]
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    xs, ts, vs = (put(a, batch_spec(mesh, a.ndim)) for a in (x, t, v))
    ys = put(y, catalog_spec(mesh))
    for a, rows in ((xs, x.shape[0] // dp), (ys, y.shape[0] // tp)):
        if len(a.sharding.device_set) != mesh.size or \
                a.addressable_shards[0].data.shape[0] != rows:
            raise AssertionError(f"array not spread over {mesh.size} "
                                 f"devices: {a.sharding}")

    def f(xx, yy, tt, vv):
        return sce_loss_sharded(xx, yy, tt, key=key, cfg=sce_cfg, mesh=mesh,
                                valid_mask=vv, mode=mode)

    def r(xx, yy):
        return sce_loss_sharded_ref(xx, yy, t, key=key, cfg=sce_cfg,
                                    dp_size=dp, valid_mask=v, mode=mode,
                                    tp_size=tp)

    with _highest(), jax.set_mesh(mesh):
        compiled = jax.jit(jax.value_and_grad(f, argnums=(0, 1))).lower(
            xs, ys, ts, vs).compile()
        _require_kernels(compiled.as_text(), ("mips_topk", "sce_gather"))
        loss, (gx, gy) = compiled(xs, ys, ts, vs)
        loss_r, (gx_r, gy_r) = jax.jit(
            jax.value_and_grad(r, argnums=(0, 1)))(x, y)
        ex_x, ex_y, n_amb, n_buckets = jax.jit(
            _selection_ties, static_argnums=(5, 6, 7, 8)
        )(x, y, t, v, key, sce_cfg, dp, tp, mode)
    rel = abs(float(loss) - float(loss_r)) / abs(float(loss_r))
    n_amb = [int(a) for a in n_amb]
    grads = {name: _grad_agreement(a, b, ex) for name, a, b, ex in
             (("dX", gx, gx_r, ex_x), ("dY", gy, gy_r, ex_y))}
    shown = (f"loss {float(loss):.7f} vs {float(loss_r):.7f} (rel {rel:.1e}); "
             f"{n_amb[0]}/{int(n_buckets)} buckets near a tie ({n_amb[1]} "
             f"at the b_x cut, {n_amb[2]} at the b_y cut, {n_amb[3]} in the "
             f"bucket max); " + ", ".join(
                 f"{k} rel {g['err']:.1e} over {g['compared']} of "
                 f"{g['nonzero']} nonzero rows ({g['err_all']:.1e} over all), "
                 f"{g['off']} rows off" for k, g in grads.items()))
    ok = (rel <= LOSS_RTOL and all(
        not g["unexplained"] and g["err"] <= GRAD_RTOL
        and g["compared"] >= COMPARED_SHARE * g["nonzero"]
        for g in grads.values()))
    if not ok:
        raise AssertionError(
            f"mesh ({dp},{tp}) {mode}: {shown}; rows off outside the "
            f"near-tie buckets (row: relative row error): "
            f"{ {k: g['unexplained'] for k, g in grads.items()} }")
    return shown


def _cutoff_ties(scores, k):
    """Buckets (rows of ``scores``) whose k-th and (k+1)-th largest
    scores lie within SELECT_TIE of each other, and the entries inside
    that band, which two float32 computations may rank either side of
    the cut."""
    import jax
    import jax.numpy as jnp

    from repro.core.sce import NEG_INF

    n_b, n = scores.shape
    if k >= n:
        return jnp.zeros((n_b,), bool), jnp.zeros(scores.shape, bool)
    top = jax.lax.top_k(scores, k + 1)[0]
    hi, lo = top[:, k - 1], top[:, k]
    finite = scores > NEG_INF / 2
    band = SELECT_TIE * jnp.max(jnp.where(finite, jnp.abs(scores), 0.0), axis=1)
    amb = hi - lo <= band
    risk = (amb[:, None] & finite & (scores >= (lo - band)[:, None])
            & (scores <= (hi + band)[:, None]))
    return amb, risk


def _selection_ties(x, y, t, v, key, sce_cfg, dp, tp, mode):
    """From the reference alone: the dX and dY rows that a near-tie in
    SCE's selection or in its per-position bucket max can reach.

    * a position near the b_x cut of bucket b: its dX row, its target's
      and bucket b's candidates' dY rows;
    * an item near the b_y cut of bucket b: its dY row, and every dX and
      dY row of bucket b, positions' targets included (the item enters
      each position's softmax);
    * a position whose two largest bucket losses nearly tie: its dX row,
      its target's dY row and the candidates' dY rows of the tied
      buckets.

    Returns (dX rows, dY rows, buckets with a near-tie — in all and at
    the b_x cut, the b_y cut and the max —, buckets)."""
    import jax.numpy as jnp

    from repro.core.distributed_sce import sce_ref_shards

    shards = sce_ref_shards(x, y, t, key=key, cfg=sce_cfg, dp_size=dp,
                            valid_mask=v, mode=mode, tp_size=tp)
    ex_x, ex_y = [], jnp.zeros((y.shape[0],), jnp.int32)
    n_amb = jnp.zeros((4,), jnp.int32)  # any, b_x cut, b_y cut, max
    for s in shards:
        idx_x, idx_y, t_l = s["idx_x"], s["idx_y"], s["targets"]
        n_l = s["xp"].shape[1]
        amb_x, risk_x = _cutoff_ties(s["xp"], s["b_x"])
        amb_y, risk_y = jnp.zeros_like(amb_x), []
        for scores, k, _ in s["y_scores"]:
            a, r = _cutoff_ties(scores, k)
            amb_y, risk_y = amb_y | a, risk_y + [r]
        risk_y = jnp.concatenate(risk_y, axis=1)
        losses, flat = s["losses"], idx_x.reshape(-1)
        top = jnp.full((n_l,), -jnp.inf).at[flat].max(losses.reshape(-1))
        near = losses >= top[idx_x] - MAX_TIE * jnp.abs(top[idx_x])
        max_tie = jnp.zeros((n_l,), jnp.int32).at[flat].add(
            near.reshape(-1).astype(jnp.int32)) > 1
        amb_m = jnp.any(near & max_tie[idx_x], axis=1)  # the tied buckets
        amb = amb_x | amb_y | amb_m
        n_amb = n_amb + jnp.stack([jnp.sum(a) for a in (amb, amb_x, amb_y, amb_m)])

        flipped = jnp.any(risk_x, axis=0) | max_tie  # (n_l,)
        rows_x = flipped.astype(jnp.int32).at[idx_x].max(
            jnp.broadcast_to(amb_y[:, None], idx_x.shape).astype(jnp.int32))
        ex_x.append(rows_x)
        whole = jnp.broadcast_to(amb[:, None], idx_y.shape).astype(jnp.int32)
        ex_y = ex_y.at[idx_y].max(whole)
        ex_y = ex_y.at[t_l[idx_x]].max(
            jnp.broadcast_to(amb_y[:, None], idx_x.shape).astype(jnp.int32))
        ex_y = ex_y.at[t_l].max(flipped.astype(jnp.int32))
        ex_y = ex_y | jnp.any(risk_y, axis=0).astype(jnp.int32)
    n_buckets = len(shards) * shards[0]["idx_x"].shape[0]
    return (jnp.concatenate(ex_x) > 0, ex_y > 0, n_amb, n_buckets)


def _grad_agreement(got, want, excused):
    """Row-wise comparison of two (rows, d) gradients. ``off`` counts
    rows that differ by over 1% of the rms row norm, ``unexplained``
    maps (up to five of) those not in ``excused`` to their relative
    error; ``compared`` counts the rows with
    a nonzero reference gradient that are not excused; ``err`` is the
    relative Frobenius error over the rows not excused, ``err_all`` over
    every row."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    excused = np.asarray(excused)
    diff = got - want
    off = np.linalg.norm(diff, axis=1) > (
        1e-2 * np.linalg.norm(want) / np.sqrt(len(want)))
    keep = ~excused
    nonzero = np.any(want != 0, axis=1)
    return dict(
        nonzero=int(nonzero.sum()), compared=int((nonzero & keep).sum()),
        off=int(off.sum()),
        unexplained={int(r): float(np.linalg.norm(diff[r])
                                   / max(np.linalg.norm(want[r]), 1e-30))
                     for r in np.nonzero(off & keep)[0][:5]},
        err=float(np.linalg.norm(diff[keep]) / np.linalg.norm(want[keep])),
        err_all=float(np.linalg.norm(diff) / np.linalg.norm(want)),
    )


def phase_shard_serve(seed):
    from repro.launch.mesh import make_mesh

    return phase_serve(seed, mesh=make_mesh((1, 4), ("data", "model")))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train, eval and serve on one chip; 4: only "
                         "the catalog-sharded SCE loss and serve")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if os.environ.get("REPRO_FORCE_INTERPRET"):
        fail("REPRO_FORCE_INTERPRET is set: the kernels would not run "
             "as Mosaic")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < args.chips:
        fail(f"{args.chips} chips asked, {len(devices)} present")
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print(f"devices: {len(devices)} x {devices[0].device_kind}", flush=True)

    phases = Phases()
    if args.chips == 1:
        phases.run("train", lambda: phase_train(args.seed))
        phases.run("eval", lambda: phase_eval(args.seed))
        phases.run("serve", lambda: phase_serve(args.seed))
    else:
        phases.run("shard", lambda: phase_shard(args.seed))
        phases.run("serve4", lambda: phase_shard_serve(args.seed))
    phases.table()
    if not phases.ok:
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": args.chips,
    }}))


if __name__ == "__main__":
    main()
