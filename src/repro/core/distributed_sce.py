"""Distributed SCE — vocab-parallel MIPS under ``shard_map`` (DESIGN.md §2/§4).

Data layout (mesh axes ``("data", "model")`` or ``("pod", "data", "model")``):
  * ``X`` (model outputs, N×d)  — rows sharded over the data axes;
  * ``Y`` (catalog,      C×d)  — rows sharded over ``model`` (vocab-parallel);
  * buckets are drawn **per data shard** (the paper re-draws ``B`` every
    batch anyway, so per-shard draws are a faithful randomized variant —
    recorded as an assumption change in DESIGN.md §2).

Both distribution strategies share one skeleton — per-shard streaming
stage-1 selection (``kernels.ops.mips_topk`` when ``cfg.use_kernel``:
the ``(n_b, C_local)`` score matrix never exists), an ownership-masked
in-bucket partial logsumexp against the LOCAL catalog slice, and a
log-space cross-shard merge (one pmax + one psum of ``(n_b, b_x)``
floats, ~1 MB). They differ only in the candidate SET:

``"exact"`` — ids-only exact MIPS: every model shard merges the
  per-shard local top-min(b_y, C/m) (value, id) pairs through
  ``dist.collectives.distributed_topk_from_local`` — the exact global
  top-b_y, tie order included, replicated over ``model``. Each shard
  then evaluates only the candidates it OWNS (ids inside its catalog
  slice; the rest are masked with the negative-id rule) and the psum
  merge reassembles the exact full-candidate denominator. Identical
  selection to a single-device run → the equality tests. Candidate
  *embeddings never cross the wire* — the old implementation shipped
  ``(n_b, b_y/m, d)`` embedding-row triples through an all_to_all,
  which dominated the payload at LM widths (d ≥ 2304); the ids-only
  exchange is ``d/2``× smaller.

``"union"`` — the TPU-native approximate mode (beyond-paper §Perf
  optimization): every shard keeps its local top-(b_y/m) — NO candidate
  exchange at all. The candidate set is the per-shard-balanced union of
  local top-(b_y/m) — same size b_y, same hard-negative intent,
  slightly different members than exact global top-b_y (both are
  approximate MIPS; the paper's bucket selection is itself a
  heuristic). Deterministically reproducible by
  ``sce_loss_sharded_ref(..., mode="union")``.

With ``cfg.use_kernel`` the in-bucket partials run through the
scalar-prefetch gather kernel (``kernels.ops.sce_gather_plse``) — the
``(n_b, b_y, d)`` candidate gather and its VJP scatter never exist; the
local ``dY`` accumulates straight into the ``(C_local, d)`` gradient.

The full ``(n_b, C)`` score matrix and the ``(N, C)`` logit matrix never
exist on any device in either mode.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.sce import NEG_INF, SCEConfig, apply_softcap, make_bucket_centers
from repro.dist.collectives import distributed_topk_from_local
from repro.dist.sharding import batch_spec, catalog_spec, data_axes, replicated_spec


def round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def _data_shard_index(dp: Tuple[str, ...]) -> jax.Array:
    """Flattened index of this device's data shard across the dp axes."""
    idx = jnp.zeros((), jnp.int32)
    for ax in dp:
        idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    return idx


def _positive_logits(x_l, y_l, t_l, tp, softcap):
    """Vocab-parallel positive-logit lookup: one psum; targets are
    identical across model shards so the elementwise sum is the gather."""
    c_local = y_l.shape[0]
    shard = jax.lax.axis_index(tp)
    local = t_l - shard * c_local
    ok = (local >= 0) & (local < c_local)
    rows = jnp.take(y_l, jnp.clip(local, 0, c_local - 1), axis=0)
    rows = jnp.where(ok[..., None], rows, 0)
    pos_emb = jax.lax.psum(rows, tp)  # (N_local, d)
    return apply_softcap(jnp.einsum("nd,nd->n", x_l, pos_emb), softcap)


def _aggregate(per_bucket_losses, idx_x, n_local, vm_l, axes):
    """Cross-bucket max per position → mean over covered → global mean."""
    per_pos = jax.ops.segment_max(
        per_bucket_losses.reshape(-1),
        idx_x.reshape(-1),
        num_segments=n_local,
    )
    hit = jax.ops.segment_max(
        jnp.ones_like(per_bucket_losses.reshape(-1)),
        idx_x.reshape(-1),
        num_segments=n_local,
    )
    per_pos = jnp.where(hit > 0, per_pos, NEG_INF)
    return per_pos


def _local_topk(b, rows, k, *, use_kernel, valid=None):
    """Per-shard stage-1 MIPS: streaming ``mips_topk`` kernel when
    ``use_kernel`` (the ``(n_b, C_local)`` score matrix never exists;
    inside interpret-mode ``shard_map`` this routes to the chunked
    reference — see kernels/ops.py), dense projection + ``lax.top_k``
    otherwise. Identical outputs and tie order either way whenever each
    row has ≥ k selectable columns; in the degenerate valid-starved
    case the kernel's placeholder tail slots are remapped to the first
    masked position (see ``core.sce._sanitize_placeholder_ids``), which
    matches the dense path's effect — tail slots land on positions the
    valid mask excludes from coverage."""
    if use_kernel:
        from repro.kernels import ops as _kops
        from repro.core.sce import _sanitize_placeholder_ids

        vals, idx = _kops.mips_topk(b, rows, k, valid=valid)
        return vals, _sanitize_placeholder_ids(idx, valid)
    p = b @ rows.T
    if valid is not None:
        p = jnp.where(valid[None, :], p, NEG_INF)
    return jax.lax.top_k(p, min(k, rows.shape[0]))


def _sce_inner(
    key, x_l, y_l, t_l, vm_l, *, cfg: SCEConfig, dp, tp,
    bucket_chunks: int, exact: bool,
):
    """Shared inner for both distributed modes (module docstring).

    Per model shard: stage-1 streaming selection, ownership-masked
    in-bucket partial LSE over the LOCAL catalog slice (computed for ALL
    buckets in ``bucket_chunks`` rematerialized chunks — peak is one
    chunk's gather), then ONE log-space pmax/psum merge across
    ``model``. ``exact`` selects the candidate set: exact global
    top-b_y ids via ``distributed_topk_from_local`` vs the local
    top-(b_y/m) union.
    """
    n_local, d = x_l.shape
    c_local = y_l.shape[0]
    m = jax.lax.psum(1, tp)
    tp_i = jax.lax.axis_index(tp)

    n_b = cfg.n_buckets
    b_x = min(cfg.bucket_size_x, n_local)
    use_kernel = cfg.use_kernel

    key_l = jax.random.fold_in(key, _data_shard_index(dp))
    b = make_bucket_centers(
        key_l, x_l, n_b, use_mix=cfg.use_mix, valid_mask=vm_l
    )

    # X side: ALL buckets on every shard (needed for the local partials).
    xs = jax.lax.stop_gradient(x_l)
    _, idx_x = _local_topk(
        b, xs, b_x, use_kernel=use_kernel, valid=vm_l
    )  # (n_b, b_x)

    # Y side: per-shard stage-1 over the local catalog slice.
    ys = jax.lax.stop_gradient(y_l)
    if exact:
        # Stage 1 clips per catalog SLICE, the merge per full catalog —
        # mirroring sce_loss_sharded_ref's min(b_y, C) clip so the
        # equality holds even when bucket_size_y > C/m (a shard then
        # simply contributes its whole slice).
        b_y_loc = min(cfg.bucket_size_y, c_local)
        vals_l, idx_l = _local_topk(b, ys, b_y_loc, use_kernel=use_kernel)
        gids_l = idx_l + tp_i * c_local
        # ids-only exact merge, replicated over ``model`` (tie order =
        # dense lax.top_k — same candidates as the single-device oracle).
        _, cand_gids = distributed_topk_from_local(
            vals_l, gids_l, cfg.bucket_size_y, tp
        )  # (n_b, min(b_y, C))
        local = cand_gids - tp_i * c_local
        own = jnp.logical_and(local >= 0, local < c_local)
        idx_y = jnp.clip(local, 0, c_local - 1)  # gather rows (clipped)
        # Non-owned candidates are evaluated on their home shard; mask
        # them here with the negative-id rule shared by kernels and refs.
        gidx_y = jnp.where(own, cand_gids, -1)
        k_cand = cand_gids.shape[-1]
    else:
        # Union mode: local top-(b_y/m) per bucket — no communication.
        k_cand = max(1, min(cfg.bucket_size_y // m, c_local))
        _, idx_y = _local_topk(b, ys, k_cand, use_kernel=use_kernel)
        gidx_y = idx_y + tp_i * c_local

    pos_logit_all = _positive_logits(x_l, y_l, t_l, tp, cfg.logit_softcap)

    while n_b % bucket_chunks:
        bucket_chunks -= 1
    nb_c = n_b // bucket_chunks

    def chunk_partials(chunk):
        """One bucket chunk → partial LSE over locally-owned candidates.
        Rematerialized so the backward never stacks the (n_b, b_x, d)
        gathers. Kernel-backed on TPU: ops.sce_gather_plse prefetch-
        gathers the candidate rows from the local catalog slice and
        accumulates dY straight into (C_local, d)."""
        idx_x_c, idx_y_c, gidx_c = chunk
        x_b = jnp.take(x_l, idx_x_c, axis=0)  # (nb_c, b_x, d)
        tgt_b = jnp.take(t_l, idx_x_c, axis=0)
        if use_kernel:
            from repro.kernels import ops as _kops

            return _kops.sce_gather_plse(
                x_b, y_l, idx_y_c, tgt_b, gidx_c,
                logit_softcap=cfg.logit_softcap,
            )
        y_b = jnp.take(y_l, idx_y_c, axis=0)  # (nb_c, k_cand, d)
        neg = apply_softcap(
            jnp.einsum("nxd,nyd->nxy", x_b, y_b), cfg.logit_softcap
        )
        collide = gidx_c[:, None, :] == tgt_b[:, :, None]
        invalid = jnp.logical_or(collide, (gidx_c < 0)[:, None, :])
        neg = jnp.where(invalid, NEG_INF, neg).astype(jnp.float32)
        mx = jnp.max(neg, axis=-1)  # (nb_c, b_x)
        sx = jnp.sum(jnp.exp(neg - mx[..., None]), axis=-1)
        return mx + jnp.log(jnp.maximum(sx, 1e-30))

    chunks = (
        idx_x.reshape(bucket_chunks, nb_c, b_x),
        idx_y.reshape(bucket_chunks, nb_c, k_cand),
        gidx_y.reshape(bucket_chunks, nb_c, k_cand),
    )
    plse = jax.lax.map(
        jax.checkpoint(chunk_partials, prevent_cse=False), chunks
    ).reshape(n_b, b_x)

    # log-space merge across model shards: one pmax + one psum (~1 MB).
    # pmax runs on a stopped-gradient copy — the max shift in a logsumexp
    # is gradient-neutral, and pmax has no differentiation rule.
    g_m = jax.lax.pmax(jax.lax.stop_gradient(plse), tp)
    g_s = jax.lax.psum(jnp.exp(plse - g_m), tp)
    pos_logit = jnp.take(pos_logit_all, idx_x, axis=0).astype(jnp.float32)
    lse = jnp.logaddexp(g_m + jnp.log(jnp.maximum(g_s, 1e-30)), pos_logit)
    losses = lse - pos_logit  # (n_b, b_x)

    per_pos = _aggregate(losses, idx_x, n_local, vm_l, dp)
    covered = (per_pos > NEG_INF / 2) & vm_l
    per_pos = jnp.where(covered, per_pos, 0.0)

    # The pmax/psum merge already made the losses model-invariant, so the
    # final reduction runs over the data axes only.
    num = jax.lax.psum(jnp.sum(per_pos), tuple(dp))
    den = jax.lax.psum(jnp.sum(covered.astype(per_pos.dtype)), tuple(dp))
    return num / jnp.maximum(den, 1.0)


def sce_loss_sharded(
    x: jax.Array,  # (N, d) global
    y: jax.Array,  # (C, d) global
    targets: jax.Array,  # (N,)
    *,
    key: jax.Array,
    cfg: SCEConfig,
    mesh: Mesh,
    valid_mask: Optional[jax.Array] = None,
    mode: str = "exact",
    bucket_chunks: Optional[int] = None,
):
    """Distributed SCE loss (see module docstring).

    ``cfg.n_buckets`` is rounded up to a multiple of the model-axis size
    (historical invariant kept so configs reproduce across versions;
    callers that need paper-exact ``n_b`` should pass a pre-rounded
    config). ``bucket_chunks`` controls the rematerialized bucket
    chunking of the partial-LSE stage (default: the model-axis size).
    """
    dp = data_axes(mesh)
    tp = "model"
    m = mesh.shape[tp]
    if cfg.n_buckets % m != 0:
        cfg = dataclasses.replace(cfg, n_buckets=round_up(cfg.n_buckets, m))
    if valid_mask is None:
        valid_mask = jnp.ones(x.shape[:1], bool)

    if mode not in ("exact", "union"):
        raise ValueError(mode)
    inner = functools.partial(
        _sce_inner, cfg=cfg, dp=dp, tp=tp,
        bucket_chunks=bucket_chunks or m, exact=(mode == "exact"),
    )
    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            replicated_spec(),
            batch_spec(mesh, 2),
            catalog_spec(mesh),
            batch_spec(mesh, 1),
            batch_spec(mesh, 1),
        ),
        out_specs=replicated_spec(),
    )
    return fn(key, x, y, targets, valid_mask)


def sce_ref_shards(
    x: jax.Array,
    y: jax.Array,
    targets: jax.Array,
    *,
    key: jax.Array,
    cfg: SCEConfig,
    dp_size: int,
    valid_mask: Optional[jax.Array] = None,
    mode: str = "exact",
    tp_size: int = 1,
):
    """The terms of :func:`sce_loss_sharded_ref`, one dict per data shard.

    Keys: ``xp`` ``(n_b, N/dp)`` position selection scores (``NEG_INF``
    where masked) and ``b_x``; ``y_scores``, a list of ``(scores, k,
    offset)`` — the ``(n_b, C_j)`` candidate scores of each catalog block
    that is ranked on its own (the whole catalog in exact mode, each
    model shard's slice in union mode), how many it selects, and its
    first global id; ``idx_x`` ``(n_b, b_x)`` local position ids;
    ``idx_y`` ``(n_b, k)`` global candidate ids; ``losses`` ``(n_b,
    b_x)`` per-(bucket, position) CE; ``targets`` and ``valid`` of the
    shard. Scores are computed at the ambient matmul precision."""
    if cfg.n_buckets % tp_size != 0:  # same rounding as the sharded path
        cfg = dataclasses.replace(
            cfg, n_buckets=round_up(cfg.n_buckets, tp_size)
        )
    n = x.shape[0]
    assert n % dp_size == 0
    n_l = n // dp_size
    c = y.shape[0]
    if valid_mask is None:
        valid_mask = jnp.ones((n,), bool)

    shards = []
    for i in range(dp_size):
        x_i = x[i * n_l : (i + 1) * n_l]
        t_i = targets[i * n_l : (i + 1) * n_l]
        vm_i = valid_mask[i * n_l : (i + 1) * n_l]
        key_i = jax.random.fold_in(key, i)
        b = make_bucket_centers(
            key_i, x_i, cfg.n_buckets, use_mix=cfg.use_mix, valid_mask=vm_i
        )
        xs = jax.lax.stop_gradient(x_i)
        ys = jax.lax.stop_gradient(y)
        xp = jnp.where(vm_i[None, :], b @ xs.T, NEG_INF)
        b_x = min(cfg.bucket_size_x, n_l)
        _, idx_x = jax.lax.top_k(xp, b_x)

        if mode == "exact":
            # same clip as the sharded path: at most the full catalog
            y_scores = [(b @ ys.T, min(cfg.bucket_size_y, c), 0)]
        else:  # union of per-shard top-(b_y/m) over catalog slices
            c_l = c // tp_size
            k_local = max(1, min(cfg.bucket_size_y // tp_size, c_l))
            y_scores = [
                (b @ ys[j * c_l : (j + 1) * c_l].T, k_local, j * c_l)
                for j in range(tp_size)
            ]
        idx_y = jnp.concatenate(
            [jax.lax.top_k(s, k)[1] + off for s, k, off in y_scores], axis=-1
        )

        x_b = jnp.take(x_i, idx_x, axis=0)
        y_b = jnp.take(y, idx_y, axis=0)
        tgt_b = jnp.take(t_i, idx_x, axis=0)
        pos_logit = apply_softcap(
            jnp.einsum("nxd,nxd->nx", x_b, jnp.take(y, tgt_b, axis=0)),
            cfg.logit_softcap,
        )
        neg = apply_softcap(
            jnp.einsum("nxd,nyd->nxy", x_b, y_b), cfg.logit_softcap
        )
        collide = idx_y[:, None, :] == tgt_b[:, :, None]
        neg = jnp.where(collide, NEG_INF, neg)
        all_logits = jnp.concatenate([pos_logit[..., None], neg], axis=-1)
        losses = jax.nn.logsumexp(all_logits, axis=-1) - pos_logit
        shards.append(dict(
            xp=xp, b_x=b_x, y_scores=y_scores, idx_x=idx_x, idx_y=idx_y,
            losses=losses, targets=t_i, valid=vm_i,
        ))
    return shards


def sce_loss_sharded_ref(
    x: jax.Array,
    y: jax.Array,
    targets: jax.Array,
    *,
    key: jax.Array,
    cfg: SCEConfig,
    dp_size: int,
    valid_mask: Optional[jax.Array] = None,
    mode: str = "exact",
    tp_size: int = 1,
):
    """Single-device oracle for :func:`sce_loss_sharded`.

    ``mode="exact"``: full-catalog candidate top-k (the two-stage
    distributed top-k is exact → same selection).
    ``mode="union"``: per-model-shard top-(b_y/m) over each catalog slice,
    concatenated — bit-matches the union mode's candidate set.
    """
    num = jnp.zeros((), jnp.float32)
    den = jnp.zeros((), jnp.float32)
    for s in sce_ref_shards(
        x, y, targets, key=key, cfg=cfg, dp_size=dp_size,
        valid_mask=valid_mask, mode=mode, tp_size=tp_size,
    ):
        n_l, idx_x = s["xp"].shape[1], s["idx_x"]
        per_pos = jax.ops.segment_max(
            s["losses"].reshape(-1), idx_x.reshape(-1), num_segments=n_l
        )
        hit = jax.ops.segment_max(
            jnp.ones((idx_x.size,), jnp.float32),
            idx_x.reshape(-1),
            num_segments=n_l,
        )
        covered = (hit > 0) & s["valid"]
        num = num + jnp.sum(jnp.where(covered, per_pos, 0.0))
        den = den + jnp.sum(covered.astype(jnp.float32))
    return num / jnp.maximum(den, 1.0)
