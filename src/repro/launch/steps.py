"""Step-function factories for every (family × shape kind).

A *step* is a pure jit-able function; the cell builder (cells.py) wires
it to abstract inputs + shardings for the dry-run, and train.py/serve.py
call the same factories for real execution on the host mesh — one code
path for both.

Training steps implement (DESIGN.md §4):
  * microbatched gradient accumulation (``lax.scan``; f32 accumulators,
    bf16 for the 1T arch);
  * the SCE loss in one of three modes:
      - ``"union"``  — shard_map distributed SCE, per-shard candidates +
        log-space merge (production default for LM archs);
      - ``"exact"``  — shard_map distributed SCE with exact two-stage
        MIPS (seqrec default; selection identical to single-device);
      - ``"gspmd"``  — the paper-literal global-bucket SCE, left to
        GSPMD to partition (the §Perf baseline);
  * optional int8 error-feedback gradient compression inside the wrapped
    optimizer (shrinks the cross-pod DCI payload);
  * MoE aux load-balance loss folded in.

The seqrec and LM train steps name their phases for the profiler
(``jax.named_scope``, HLO ``op_name`` metadata only): ``backbone``
(embedding lookup and encoder, forward and backward), ``select`` and
``gather_loss`` (inside ``core/distributed_sce``) and ``optim`` (the
guarded optimizer update).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.distributed_sce import round_up, sce_loss_sharded
from repro.core.losses import ce_chunked, make_loss
from repro.core.sce import SCEConfig, sce_loss
from repro.dist.collectives import distributed_topk, distributed_topk_from_local
from repro.dist.sharding import batch_spec, catalog_spec, replicated_spec
from repro.eval.streaming import streaming_topk
from repro.launch.mesh import dp_size
from repro.models import bert4rec as b4r_lib
from repro.models import recsys as recsys_lib
from repro.models import sasrec as sasrec_lib
from repro.models import schnet as schnet_lib
from repro.models import transformer as tf_lib
from repro.optim import make_optimizer
from repro.optim.optimizers import global_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------
def _pop_loss_cap(batch):
    """Split the optional ``"loss_cap"`` scalar out of a train batch.

    The divergence guard (``launch/elastic.py``) feeds its dynamic cap
    into the jitted step as an ordinary batch entry — a 0-d f32 array,
    so changing the cap never retraces — and the step factories pop it
    before microbatch reshaping. Batches without the entry (cells.py
    dry-run lowering, direct step calls in tests) run unguarded against
    an infinite cap."""
    batch = dict(batch)
    return batch, batch.pop("loss_cap", None)


def _apply_update_guarded(opt_update, loss, grads, params, opt_state,
                          loss_cap=None, sentinels=None):
    """Optimizer update gated on step health (DESIGN.md §8).

    ``ok`` = loss finite AND global grad norm finite AND (when a cap is
    provided) loss ≤ cap. On a bad step params AND optimizer state are
    kept bit-identical (the step counter does not advance — a skipped
    step never happened as far as schedules/moments are concerned).
    Surfaced metrics: ``loss``, ``skipped`` (the on-device skip
    decision), ``grad_norm``, and (when the loss threaded them) the
    guard's per-kernel ``sentinels`` counter dict — the host-side
    divergence guard keys on ``skipped`` rather than re-deriving
    finiteness from a float round trip, and on a strike the sentinels
    name WHICH kernel went non-finite (kernels/guard/sentinels.py)."""
    with jax.named_scope("optim"):
        gnorm = global_norm(grads)
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        if loss_cap is not None:
            ok &= loss <= loss_cap
        new_params, new_opt = opt_update(grads, opt_state, params)
        keep = lambda new, old: jax.tree.map(
            lambda n, o: jnp.where(ok, n, o), new, old
        )
        metrics = {"loss": loss, "skipped": ~ok, "grad_norm": gnorm}
        if sentinels:
            metrics["sentinels"] = dict(sentinels)
        return keep(new_params, params), keep(new_opt, opt_state), metrics


def build_sce_config(
    n_positions_local: int,
    catalog: int,
    *,
    bucket_size_y: int,
    tp: int = 1,
    use_mix: bool = True,
    use_kernel: bool = True,
    logit_softcap: Optional[float] = None,
    alpha: float = 2.0,
    beta: float = 1.0,
) -> SCEConfig:
    """Paper parametrization (§4.2.1) from the per-shard position count,
    with ``n_b`` rounded up to the model-axis size for even bucket
    splitting."""
    cfg = SCEConfig.from_alpha_beta(
        n_positions_local,
        catalog,
        alpha=alpha,
        beta=beta,
        bucket_size_y=bucket_size_y,
        use_mix=use_mix,
        use_kernel=use_kernel,
    )
    import dataclasses

    return dataclasses.replace(
        cfg,
        n_buckets=round_up(cfg.n_buckets, tp),
        logit_softcap=logit_softcap,
    )


# Which kernel a loss name's sentinel counters should blame — the
# kernel group (kernels/guard/conformance.py registry key) the loss
# dispatches to. Names outside the map use the loss name itself.
_SENTINEL_KERNEL = {
    "sce": "sce_bucket",
    "ce_fused": "fused_ce",
    "ce_fused_linear": "linear_sce",
}


def _vocab_loss(
    x, y, targets, valid, key, *, loss_name, sce_cfg, sce_mode, mesh,
    logit_softcap: Optional[float] = None,
):
    """Dispatch the LM-head / catalog loss.

    sce_mode: "exact" | "union" (shard_map distributed SCE variants, see
    core/distributed_sce.py) | "gspmd" (global-bucket paper-literal SCE,
    partitioned by GSPMD — the §Perf baseline).

    ``logit_softcap`` (gemma-2 final-logit cap) reaches every CE
    variant that supports it: the SCE paths carry it inside
    ``sce_cfg``; ``ce_chunked`` caps inside its streaming scan;
    ``ce_fused_linear`` caps inside the Pallas tile.

    Returns ``(loss, sentinels)`` — the guard's on-device numerics
    counter dict (``kernels/guard/sentinels.py``), keyed by the kernel
    the loss dispatched to, empty under guard policy ``off``.
    """
    from repro.kernels import guard

    if loss_name == "sce":
        if sce_mode in ("exact", "union") and mesh is not None:
            loss = sce_loss_sharded(
                x, y, targets, key=key, cfg=sce_cfg, mesh=mesh,
                valid_mask=valid, mode=sce_mode,
            )
        else:
            loss = sce_loss(
                x, y, targets, key=key, cfg=sce_cfg, valid_mask=valid
            )
        aux = {}
    elif loss_name == "ce_chunked":
        loss, aux = ce_chunked(
            x, y, targets, valid_mask=valid, logit_softcap=logit_softcap
        )
    elif loss_name == "ce_fused_linear":
        from repro.core.losses import ce_fused_linear

        loss, aux = ce_fused_linear(
            x, y, targets, valid_mask=valid, logit_softcap=logit_softcap
        )
    else:
        fn = make_loss(loss_name)
        loss, aux = fn(x, y, targets, valid_mask=valid, key=key)
    if guard.policy() == "off":
        return loss, {}
    sentinels = aux.get("sentinels")
    if sentinels is None:
        # the guard's finiteness counters, profiled with its update gate
        with jax.named_scope("optim"):
            sentinels = guard.loss_sentinels(
                _SENTINEL_KERNEL.get(loss_name, loss_name), loss
            )
    return loss, sentinels


def _accumulate_microbatches(
    loss_and_grad_fn, params, batch, key, n_micro, accum_dtype=jnp.float32,
    *, with_aux=False,
):
    """lax.scan over microbatches; mean-accumulated grads in
    ``accum_dtype`` (f32 default; bf16 for params-dominated giants).

    ``with_aux=False`` (legacy): the fn returns ``(loss, grads)``.
    ``with_aux=True``: the fn returns ``(loss, aux, grads)`` where
    ``aux`` is a dict of on-device counters (the guard's numerics
    sentinels) summed across microbatches; the result is
    ``(loss, aux, grads)``."""

    def call(mb, mb_key):
        out = loss_and_grad_fn(params, mb, mb_key)
        if with_aux:
            return out
        loss, grads = out
        return loss, {}, grads

    if n_micro == 1:
        loss, aux, grads = call(batch, key)
        return (loss, aux, grads) if with_aux else (loss, grads)

    stacked = jax.tree.map(
        lambda a: a.reshape((n_micro, a.shape[0] // n_micro) + a.shape[1:]),
        batch,
    )

    def body(carry, inp):
        acc_loss, acc_grads = carry
        mb, i = inp
        loss, aux, grads = call(mb, jax.random.fold_in(key, i))
        acc_grads = jax.tree.map(
            lambda a, g: a + g.astype(accum_dtype) / n_micro,
            acc_grads,
            grads,
        )
        return (acc_loss + loss / n_micro, acc_grads), aux

    zero_grads = jax.tree.map(
        lambda p: jnp.zeros(p.shape, accum_dtype), params
    )
    (loss, grads), auxs = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), zero_grads),
        (stacked, jnp.arange(n_micro)),
    )
    grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
    if with_aux:
        aux = jax.tree.map(lambda a: jnp.sum(a, axis=0), auxs)
        return loss, aux, grads
    return loss, grads


# ---------------------------------------------------------------------------
# LM transformers
# ---------------------------------------------------------------------------
def make_lm_train_step(
    arch,
    cfg,
    mesh,
    shape,
    *,
    sce_mode: str = "union",
    grad_compression: Optional[str] = None,
    n_micro_override: Optional[int] = None,
    bucket_size_y: Optional[int] = None,
):
    opt_init, opt_update = make_optimizer(arch.optimizer, 3e-4)
    if grad_compression == "int8":
        from repro.optim import with_error_feedback_compression

        opt_init, opt_update = with_error_feedback_compression(
            (opt_init, opt_update)
        )
    gb = shape.dims["global_batch"]
    seq = shape.dims["seq_len"]
    dp = dp_size(mesh) if mesh is not None else 1
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    # microbatch count is capped so every microbatch still spans the data
    # axes (≥1 sequence per shard)
    requested = n_micro_override or arch.microbatches.get(shape.name, 1)
    n_micro = max(1, min(requested, gb // dp))
    # paper-literal GSPMD mode draws GLOBAL buckets over the whole
    # microbatch, so its (α, β) parametrization uses global positions
    n_pos = (
        (gb // n_micro) * seq
        if sce_mode == "gspmd"
        else (gb // n_micro // dp) * seq
    )
    assert n_pos > 0, (gb, n_micro, dp)
    sce_cfg = build_sce_config(
        n_pos,
        cfg.vocab,
        bucket_size_y=bucket_size_y or arch.sce_bucket_size_y,
        tp=tp,
        logit_softcap=cfg.final_softcap,
    )

    def loss_and_grad(params, mb, key):
        def loss_fn(p):
            with jax.named_scope("backbone"):
                hidden, aux = tf_lib.forward(p, cfg, mb["tokens"])
                x = hidden.reshape(-1, hidden.shape[-1])
                # padded rows act as phantom negatives
                y = tf_lib.output_embedding(p, cfg)
            loss, sentinels = _vocab_loss(
                x,
                y,
                mb["targets"].reshape(-1),
                mb["valid"].reshape(-1),
                key,
                loss_name=arch.train_loss,
                sce_cfg=sce_cfg,
                sce_mode=sce_mode,
                mesh=mesh,
                logit_softcap=cfg.final_softcap,
            )
            return loss + aux, sentinels
        (loss, sentinels), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        return loss, sentinels, grads

    accum_dtype = jnp.dtype(arch.accum_dtype)

    def train_step(params, opt_state, batch, key):
        batch, loss_cap = _pop_loss_cap(batch)
        loss, sentinels, grads = _accumulate_microbatches(
            loss_and_grad, params, batch, key, n_micro, accum_dtype,
            with_aux=True,
        )
        # (int8 error-feedback compression, if enabled, lives inside the
        # wrapped optimizer — see optim.with_error_feedback_compression)
        return _apply_update_guarded(
            opt_update, loss, grads, params, opt_state, loss_cap,
            sentinels=sentinels,
        )

    return train_step, (opt_init, opt_update), sce_cfg


def make_lm_prefill_step(cfg, *, act_spec=None):
    def prefill_step(params, tokens):
        hidden, cache = tf_lib.prefill(
            params, cfg, tokens, act_spec=act_spec
        )
        logits = tf_lib.logits_from_hidden(params, cfg, hidden[:, -1:])
        return logits, cache

    return prefill_step


def make_lm_decode_step(cfg):
    def decode_step(params, cache, tokens, pos):
        return tf_lib.decode_step(params, cfg, cache, tokens, pos)

    return decode_step


# ---------------------------------------------------------------------------
# Sequential recommenders (bert4rec / sasrec — the paper's own domain)
# ---------------------------------------------------------------------------
def make_seqrec_train_step(
    arch, cfg, mesh, shape, *, sce_mode: str = "exact",
    grad_compression=None,
):
    opt_init, opt_update = make_optimizer(arch.optimizer, 1e-3)
    if grad_compression == "int8":
        from repro.optim import with_error_feedback_compression

        opt_init, opt_update = with_error_feedback_compression(
            (opt_init, opt_update)
        )
    gb = shape.dims["batch"]
    seq = cfg.max_len
    dp = dp_size(mesh) if mesh is not None else 1
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    n_micro = max(1, min(arch.microbatches.get(shape.name, 1), gb // dp))
    n_pos_local = (gb // n_micro // dp) * seq
    assert n_pos_local > 0, (gb, n_micro, dp)
    sce_cfg = build_sce_config(
        n_pos_local,
        cfg.n_items,
        bucket_size_y=arch.sce_bucket_size_y,
        tp=tp,
    )
    bidirectional = not cfg.causal

    def loss_and_grad(params, mb, key):
        k_mask, k_loss, k_drop = jax.random.split(key, 3)

        def loss_fn(p):
            tokens = mb["tokens"]
            with jax.named_scope("backbone"):
                if bidirectional:
                    masked, is_masked = b4r_lib.apply_cloze_mask(
                        k_mask, tokens, cfg
                    )
                    hidden = b4r_lib.forward(p, cfg, masked)
                    targets = tokens.reshape(-1)
                    valid = is_masked.reshape(-1)
                else:
                    hidden = sasrec_lib.forward(p, cfg, tokens)
                    targets = mb["targets"].reshape(-1)
                    valid = mb["valid"].reshape(-1)
                x = hidden.reshape(-1, hidden.shape[-1])
                y = sasrec_lib.loss_catalog(p, cfg)  # shard-even slice
            return _vocab_loss(
                x, y, targets, valid, k_loss,
                loss_name=arch.train_loss,
                sce_cfg=sce_cfg,
                sce_mode=sce_mode,
                mesh=mesh,
                logit_softcap=getattr(cfg, "final_softcap", None),
            )

        (loss, sentinels), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        return loss, sentinels, grads

    def train_step(params, opt_state, batch, key):
        batch, loss_cap = _pop_loss_cap(batch)
        loss, sentinels, grads = _accumulate_microbatches(
            loss_and_grad, params, batch, key, n_micro, with_aux=True
        )
        return _apply_update_guarded(
            opt_update, loss, grads, params, opt_state, loss_cap,
            sentinels=sentinels,
        )

    return train_step, (opt_init, opt_update), sce_cfg


def make_seqrec_mips_serve_step(arch, cfg, mesh, *, top_k: int = 10,
                                block_c: int = 512):
    """MIPS-backed retrieval serving (the ``launch/serve.py`` step):
    encode the request batch, then stream the (model-sharded) catalog
    through the same selection kernel the SCE training step uses
    (``kernels.ops.mips_topk`` via ``eval.streaming.streaming_topk``) —
    the inference side never materializes a ``(B, C)`` score matrix,
    mirroring the training/eval-side peak-memory argument.

    Exactness contract (pinned by the differential tests): ids, values
    and tie order (lower global id wins) are bit-identical to the dense
    masked ``lax.top_k`` oracle and to the fused eval scorer's top-k at
    the same ``[1, n_items)`` window — the padding row 0 and the
    phantom rows ``>= n_items`` never serve (the eval sweep's
    ``c_lo=1`` / ``c_hi=n_items`` masking; the superseded dense serve
    step only masked phantoms). With a mesh, the catalog rides the
    ``model`` axis and per-shard candidates merge through
    ``distributed_topk_from_local`` exactly like the sharded eval
    harness — candidate (value, global-id) pairs cross the wire, never
    embeddings.
    """
    bidirectional = not cfg.causal

    def serve_step(params, tokens):
        hidden = (
            b4r_lib.forward(params, cfg, tokens)
            if bidirectional
            else sasrec_lib.forward(params, cfg, tokens)
        )
        x_last = hidden[:, -1]  # (B, d)
        y = sasrec_lib.loss_catalog(params, cfg)  # shard-even slice

        if mesh is None:
            return streaming_topk(
                x_last, y, top_k,
                c_lo=1, c_hi=cfg.n_items, block_c=block_c,
            )

        def inner(x_l, y_l):
            c_local = y_l.shape[0]
            off = jax.lax.axis_index("model") * c_local
            vals_l, gids_l = streaming_topk(
                x_l, y_l, min(top_k, c_local),
                c_lo=1, c_hi=cfg.n_items, id_offset=off,
                block_c=block_c,
            )
            return distributed_topk_from_local(
                vals_l, gids_l, top_k, "model"
            )

        fn = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(batch_spec(mesh, 2), catalog_spec(mesh)),
            out_specs=(batch_spec(mesh, 2), batch_spec(mesh, 2)),
        )
        return fn(x_last, y)

    return serve_step


def make_seqrec_serve_step(arch, cfg, mesh, *, top_k: int = 100,
                           batch_chunk: int = 2048):
    """Score user states against the (vocab-parallel) catalog and return
    the top-k items — shard_map two-stage top-k, chunked over the batch
    so the per-chunk score slice stays small (DESIGN.md §4)."""
    bidirectional = not cfg.causal

    def serve_step(params, tokens):
        hidden = (
            b4r_lib.forward(params, cfg, tokens)
            if bidirectional
            else sasrec_lib.forward(params, cfg, tokens)
        )
        x_last = hidden[:, -1]  # (B, d)
        y = sasrec_lib.loss_catalog(params, cfg)  # shard-even slice
        c_pad = cfg.catalog_loss_size

        if mesh is None:
            scores = x_last @ y.T
            ids = jnp.arange(c_pad)
            scores = jnp.where(ids[None, :] < cfg.n_items, scores, NEG_INF)
            vals, idx = jax.lax.top_k(scores, top_k)
            return vals, idx

        def inner(x_l, y_l):
            b_l = x_l.shape[0]
            c_local = y_l.shape[0]
            shard = jax.lax.axis_index("model")
            # phantom (padding / mask-token) rows never serve
            gids = shard * c_local + jnp.arange(c_local)
            phantom = gids >= cfg.n_items
            chunk = min(batch_chunk, b_l)
            n_chunks = -(-b_l // chunk)
            pad = n_chunks * chunk - b_l
            xp = jnp.pad(x_l, ((0, pad), (0, 0))).reshape(
                n_chunks, chunk, -1
            )

            def score_chunk(xc):
                s = xc @ y_l.T  # (chunk, C_local)
                s = jnp.where(phantom[None, :], NEG_INF, s)
                vals, idx, _ = distributed_topk(s, top_k, "model")
                return vals, idx

            vals, idx = jax.lax.map(score_chunk, xp)
            # (distributed_topk already replicates over 'model')
            vals = vals.reshape(-1, top_k)[:b_l]
            idx = idx.reshape(-1, top_k)[:b_l]
            return vals, idx

        fn = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(batch_spec(mesh, 2), catalog_spec(mesh)),
            out_specs=(batch_spec(mesh, 2), batch_spec(mesh, 2)),
        )
        return fn(x_last, y)

    return serve_step


def make_seqrec_retrieval_step(arch, cfg, mesh, *, top_k: int = 100):
    """One user state vs a candidate list (≈ the catalog): masked local
    scoring + pmax over the model axis — each candidate is owned by
    exactly one shard, so the pmax assembles exact scores."""
    bidirectional = not cfg.causal

    def retrieval_step(params, tokens, candidate_ids):
        hidden = (
            b4r_lib.forward(params, cfg, tokens)
            if bidirectional
            else sasrec_lib.forward(params, cfg, tokens)
        )
        x_last = hidden[:, -1]  # (B, d) — B is 1 for retrieval_cand
        y = sasrec_lib.loss_catalog(params, cfg)  # shard-even; candidates
        # are real item ids, so phantom rows are never gathered.

        if mesh is None:
            cand = jnp.take(y, candidate_ids, axis=0)
            scores = x_last @ cand.T
            vals, idx = jax.lax.top_k(scores, top_k)
            return vals, idx

        def inner(x_g, y_l, cand_ids):
            c_local = y_l.shape[0]
            shard = jax.lax.axis_index("model")
            local = cand_ids - shard * c_local
            ok = (local >= 0) & (local < c_local)
            rows = jnp.take(y_l, jnp.clip(local, 0, c_local - 1), axis=0)
            scores = x_g @ rows.T  # (B, n_cand)
            scores = jnp.where(ok[None, :], scores, NEG_INF)
            scores = jax.lax.pmax(scores, "model")  # owner-exact + replicated
            vals, idx = jax.lax.top_k(scores, top_k)
            return vals, idx

        fn = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                replicated_spec(),
                catalog_spec(mesh),
                replicated_spec(),
            ),
            out_specs=(replicated_spec(), replicated_spec()),
        )
        return fn(x_last, y, candidate_ids)

    return retrieval_step


# ---------------------------------------------------------------------------
# CTR recsys (DCN-v2 / DLRM / xDeepFM)
# ---------------------------------------------------------------------------
_RECSYS_FWD = {
    "dcn-v2": recsys_lib.dcn_v2_forward,
    "dlrm-rm2": recsys_lib.dlrm_forward,
    "xdeepfm": recsys_lib.xdeepfm_forward,
}


def recsys_forward_fn(arch_name: str) -> Callable:
    return _RECSYS_FWD[arch_name]


def make_recsys_train_step(arch, cfg, mesh, shape, *,
                           grad_compression=None):
    opt_init, opt_update = make_optimizer(arch.optimizer, 1e-3)
    if grad_compression == "int8":
        from repro.optim import with_error_feedback_compression

        opt_init, opt_update = with_error_feedback_compression(
            (opt_init, opt_update)
        )
    fwd = recsys_forward_fn(arch.name)
    n_micro = arch.microbatches.get(shape.name, 1)

    def loss_and_grad(params, mb, key):
        def loss_fn(p):
            logits = fwd(p, cfg, mb["dense"], mb["sparse_ids"])
            return recsys_lib.bce_logits_loss(logits, mb["labels"])

        return jax.value_and_grad(loss_fn)(params)

    def train_step(params, opt_state, batch, key):
        batch, loss_cap = _pop_loss_cap(batch)
        loss, grads = _accumulate_microbatches(
            loss_and_grad, params, batch, key, n_micro
        )
        return _apply_update_guarded(
            opt_update, loss, grads, params, opt_state, loss_cap
        )

    return train_step, (opt_init, opt_update)


def make_recsys_serve_step(arch, cfg):
    fwd = recsys_forward_fn(arch.name)

    def serve_step(params, dense, sparse_ids):
        return jax.nn.sigmoid(fwd(params, cfg, dense, sparse_ids))

    return serve_step


def make_recsys_retrieval_step(arch, cfg, *, item_field: int = 0,
                               chunk: int = 4096, top_k: int = 100):
    # chunk=4096 keeps the per-chunk interaction tensor bounded — at 65536
    # xDeepFM's CIN outer product is (chunk, 200, 39, 10) f32 ≈ 20 GiB
    fwd = recsys_forward_fn(arch.name)

    def retrieval_step(params, dense_user, sparse_user, candidate_ids):
        scores = recsys_lib.retrieval_scores(
            fwd, params, cfg, dense_user, sparse_user, candidate_ids,
            item_field=item_field, chunk=chunk,
        )
        vals, idx = jax.lax.top_k(scores, top_k)
        return vals, idx

    return retrieval_step


# ---------------------------------------------------------------------------
# GNN (SchNet)
# ---------------------------------------------------------------------------
def make_gnn_train_step(arch, cfg, mesh, shape):
    opt_init, opt_update = make_optimizer(arch.optimizer, 1e-3)
    kind = shape.kind
    n_graphs = int(shape.dims.get("batch", 1))  # static (molecule shape)

    def loss_and_grad(params, batch, key):
        def loss_fn(p):
            if kind == "train_sampled":
                e, _ = schnet_lib.node_energies(
                    p,
                    cfg,
                    batch["node_feats"],
                    batch["positions"],
                    batch["edge_index"],
                    edge_valid=batch["edge_valid"],
                )
                pred = jnp.take(e, batch["seed_local"], axis=0)
                err = jnp.square(pred - batch["targets"])
                return jnp.mean(err)
            if "graph_ids" in batch:  # batched molecules → per-graph
                energy, _ = schnet_lib.forward(
                    p,
                    cfg,
                    batch["node_feats"],
                    batch["positions"],
                    batch["edge_index"],
                    batch["graph_ids"],
                    n_graphs,
                )
                return jnp.mean(jnp.square(energy - batch["targets"]))
            # full-batch node regression (padded nodes/edges masked out)
            e, _ = schnet_lib.node_energies(
                p,
                cfg,
                batch["node_feats"],
                batch["positions"],
                batch["edge_index"],
                edge_valid=batch.get("edge_valid"),
            )
            err = jnp.square(e - batch["targets"])
            if "node_valid" in batch:
                w = batch["node_valid"].astype(err.dtype)
                return jnp.sum(err * w) / jnp.maximum(jnp.sum(w), 1.0)
            return jnp.mean(err)

        return jax.value_and_grad(loss_fn)(params)

    def train_step(params, opt_state, batch, key):
        batch, loss_cap = _pop_loss_cap(batch)
        loss, grads = loss_and_grad(params, batch, key)
        return _apply_update_guarded(
            opt_update, loss, grads, params, opt_state, loss_cap
        )

    return train_step, (opt_init, opt_update)
