"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the kernels are tested against (allclose sweeps
over shapes and dtypes in tests/test_kernels.py). They intentionally
materialize the full logit tensors — memory-hungry but simple.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import vary_as

NEG_INF = -1e30
ID_PAD = jnp.iinfo(jnp.int32).max


def _masked_neg_logits(x_b, y_b, tgt_b, cand_ids, logit_softcap=None):
    """Collision- and validity-masked in-bucket negative logits (f32).

    Candidates equal to the position's target are not negatives;
    candidates with a NEGATIVE id are invalid slots (padding, or — in
    the distributed ids-only exact mode — candidates owned by another
    catalog shard) and are masked for every position. ``logit_softcap``
    (gemma-2: ``cap·tanh(logit/cap)``) applies BEFORE the mask — masked
    slots must stay at NEG_INF, not ``−cap``.
    """
    f32 = jnp.float32
    neg = jnp.einsum("nxd,nyd->nxy", x_b.astype(f32), y_b.astype(f32))
    if logit_softcap is not None:
        neg = logit_softcap * jnp.tanh(neg / logit_softcap)
    collide = cand_ids[:, None, :] == tgt_b[:, :, None]
    invalid = jnp.logical_or(collide, (cand_ids < 0)[:, None, :])
    return jnp.where(invalid, NEG_INF, neg)


def sce_bucket_loss_ref(
    x_b: jax.Array,  # (n_b, b_x, d)
    y_b: jax.Array,  # (n_b, b_y, d)
    tgt_b: jax.Array,  # (n_b, b_x) int32 target catalog ids
    cand_ids: jax.Array,  # (n_b, b_y) int32 bucket-candidate catalog ids
    pos_logit: jax.Array,  # (n_b, b_x) — already capped when softcapping
    logit_softcap=None,
) -> jax.Array:
    """In-bucket CE (Algorithm 1, lines 12–15). Returns (n_b, b_x) losses.

    ``loss = logsumexp([pos, negs]) - pos`` with candidates equal to the
    position's target (or carrying a negative = invalid id) masked out
    of the negative set.
    """
    f32 = jnp.float32
    neg = _masked_neg_logits(x_b, y_b, tgt_b, cand_ids, logit_softcap)
    pos = pos_logit.astype(f32)
    m = jnp.maximum(jnp.max(neg, axis=-1), pos)
    s = jnp.sum(jnp.exp(neg - m[..., None]), axis=-1) + jnp.exp(pos - m)
    return (m + jnp.log(s) - pos).astype(pos_logit.dtype)


def sce_bucket_plse_ref(
    x_b: jax.Array,  # (n_b, b_x, d)
    y_b: jax.Array,  # (n_b, b_y, d)
    tgt_b: jax.Array,  # (n_b, b_x) int32
    cand_ids: jax.Array,  # (n_b, b_y) int32
    logit_softcap=None,
) -> jax.Array:
    """Partial logsumexp over in-bucket negatives (collision- and
    validity-masked, no positive term) — the building block of the
    distributed partial-merge modes. → (n_b, b_x) f32."""
    neg = _masked_neg_logits(x_b, y_b, tgt_b, cand_ids, logit_softcap)
    m = jnp.max(neg, axis=-1)
    s = jnp.sum(jnp.exp(neg - m[..., None]), axis=-1)
    return m + jnp.log(jnp.maximum(s, 1e-30))


def mips_topk_ref(
    q: jax.Array,  # (n_q, d) bucket centers
    y: jax.Array,  # (C, d) catalog (or model outputs, or a shard)
    k: int,
    *,
    valid=None,  # optional (C,) bool — rows never selected when False
    chunk: int = 512,
    id_offset=0,
):
    """Chunked streaming MIPS top-k — pure-jnp reference for
    ``kernels/mips_topk.py`` (and the path used inside ``shard_map``,
    where interpret-mode Pallas cannot run — see ``kernels/ops.py``).

    ``lax.scan`` over ``(chunk, d)`` catalog slices carrying only the
    ``(n_q, k)`` value/id merge buffers; peak live score elements are
    ``O(n_q·(k + chunk))`` rather than ``O(n_q·C)``. Same outputs and
    tie rule as the kernel and as a dense masked ``lax.top_k``: each
    chunk merge concatenates the (id-ascending) running buffer before
    the new (id-ascending) columns and ``lax.top_k`` is stable, so ties
    resolve toward the lower global id.
    """
    n_q, _ = q.shape
    c = y.shape[0]
    k = min(k, c)
    chunk = min(chunk, c)
    pad = (-c) % chunk
    yp = jnp.pad(y, ((0, pad), (0, 0)))
    if valid is None:
        valid = jnp.ones((c,), bool)
    vp = jnp.pad(valid.astype(bool), (0, pad))
    n_chunks = (c + pad) // chunk
    f32 = jnp.float32
    q32 = q.astype(f32)

    vals0 = jnp.full((n_q, k), NEG_INF, f32)
    ids0 = jnp.full((n_q, k), ID_PAD, jnp.int32)

    def body(carry, jc):
        vals, ids = carry
        rows = jax.lax.dynamic_slice_in_dim(yp, jc * chunk, chunk, 0)
        ok = jax.lax.dynamic_slice_in_dim(vp, jc * chunk, chunk, 0)
        s = q32 @ rows.astype(f32).T  # (n_q, chunk)
        idx = jc * chunk + jnp.arange(chunk, dtype=jnp.int32)
        ok = jnp.logical_and(ok, idx < c)
        s = jnp.where(ok[None, :], s, NEG_INF)
        col = jnp.broadcast_to((id_offset + idx)[None, :], s.shape)
        cat_v = jnp.concatenate([vals, s], axis=-1)
        cat_i = jnp.concatenate([ids, col], axis=-1)
        v, sel = jax.lax.top_k(cat_v, k)
        i = jnp.take_along_axis(cat_i, sel, axis=-1)
        return (v, i), None

    carry0 = vary_as((vals0, ids0), q, y, valid, id_offset)
    (vals, ids), _ = jax.lax.scan(body, carry0, jnp.arange(n_chunks))
    return vals, ids


def merge_topk_tile(vals, ids, tile_vals, tile_ids, k: int):
    """``K`` first-occurrence-argmax rounds over the concatenation of a
    running top-k buffer and one tile — the fixed-cost merge
    ``topk_merge.insert_topk_tile`` replaced in the kernels, kept as
    its oracle. Each round takes the row max, locates its earliest
    concatenation position (buffer before tile, so the lower id wins a
    tie), emits ``(val, id)`` and knocks the position out.

    Parameters
    ----------
    vals : (rows, k) f32 running top-k values, descending; ``NEG_INF``
        in unfilled slots.
    ids : (rows, k) i32 matching ids; ``ID_PAD`` in unfilled slots.
    tile_vals : (rows, t) f32 masked tile scores.
    tile_ids : (rows, t) i32 global ids of the tile columns, ascending.
    k : buffer width (static).

    Returns
    -------
    (vals', ids') : the merged ``(rows, k)`` buffer, same invariants.
    """
    cat_v = jnp.concatenate([vals, tile_vals], axis=-1)
    cat_i = jnp.concatenate([ids, tile_ids], axis=-1)
    width = k + tile_vals.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, cat_v.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)

    def round_(r, carry):
        cat_v, out_v, out_i = carry
        m = jnp.max(cat_v, axis=-1, keepdims=True)
        first = jnp.min(
            jnp.where(cat_v == m, pos, width), axis=-1, keepdims=True
        )
        sel = pos == first
        sel_id = jnp.sum(jnp.where(sel, cat_i, 0), axis=-1, keepdims=True)
        sel_id = jnp.where(m == NEG_INF, ID_PAD, sel_id)
        out_v = jnp.where(slot == r, m, out_v)
        out_i = jnp.where(slot == r, sel_id, out_i)
        return jnp.where(sel, NEG_INF, cat_v), out_v, out_i

    _, out_v, out_i = jax.lax.fori_loop(
        0, k, round_,
        (cat_v, jnp.full_like(vals, NEG_INF), jnp.full_like(ids, ID_PAD)),
    )
    return out_v, out_i


def eval_topk_ref(
    x: jax.Array,  # (B, d)
    y: jax.Array,  # (C, d) catalog (or a catalog shard)
    tgt_scores: jax.Array,  # (B,) f32 target score per row
    k: int,
    *,
    chunk: int = 512,
    c_lo: int = 0,
    c_hi=None,
    id_offset=0,
):
    """Chunked streaming top-k + rank counts — pure-jnp reference for
    ``kernels/eval_topk.py`` (and the path used inside ``shard_map``,
    where interpret-mode Pallas cannot run — see ``kernels/ops.py``).

    ``lax.scan`` over ``(chunk, d)`` catalog slices carrying only
    ``(topk_vals, topk_ids, gt, eq)``; peak live elements are
    ``O(B·(k + chunk))`` rather than ``O(B·C)``. Columns with global id
    outside ``[c_lo, c_hi)`` are masked (padding / phantom rows);
    ``id_offset`` (may be traced, e.g. ``shard * C_local``) maps local
    rows of ``y`` to global catalog ids. Same outputs and tie rule as
    the kernel: ties resolve toward the lower global id because each
    chunk merge concatenates the (id-ascending) running buffer before
    the new (id-ascending) columns and ``lax.top_k`` is stable.
    """
    b, _ = x.shape
    c = y.shape[0]
    if c_hi is None:
        c_hi = id_offset + c
    chunk = min(chunk, c)
    pad = (-c) % chunk
    yp = jnp.pad(y, ((0, pad), (0, 0)))
    n_chunks = (c + pad) // chunk
    f32 = jnp.float32
    x32 = x.astype(f32)
    tgt = tgt_scores.astype(f32)[:, None]

    vals0 = jnp.full((b, k), NEG_INF, f32)
    ids0 = jnp.full((b, k), ID_PAD, jnp.int32)
    cnt0 = jnp.zeros((b,), jnp.int32)

    def body(carry, jc):
        vals, ids, gt, eq = carry
        rows = jax.lax.dynamic_slice_in_dim(yp, jc * chunk, chunk, 0)
        s = x32 @ rows.astype(f32).T  # (b, chunk)
        idx = jc * chunk + jnp.arange(chunk, dtype=jnp.int32)
        col = jnp.broadcast_to((id_offset + idx)[None, :], s.shape)
        # padded-tail rows (idx ≥ C) masked explicitly — their global ids
        # may alias the next catalog shard's range
        valid = jnp.logical_and(
            jnp.broadcast_to((idx < c)[None, :], s.shape),
            jnp.logical_and(col >= c_lo, col < c_hi),
        )
        s = jnp.where(valid, s, NEG_INF)
        gt = gt + jnp.sum((s > tgt).astype(jnp.int32), axis=-1)
        eq = eq + jnp.sum((s == tgt).astype(jnp.int32), axis=-1)
        cat_v = jnp.concatenate([vals, s], axis=-1)
        cat_i = jnp.concatenate([ids, col], axis=-1)
        v, sel = jax.lax.top_k(cat_v, k)
        i = jnp.take_along_axis(cat_i, sel, axis=-1)
        return (v, i, gt, eq), None

    carry0 = vary_as((vals0, ids0, cnt0, cnt0), x, y, tgt_scores, id_offset)
    (vals, ids, gt, eq), _ = jax.lax.scan(body, carry0, jnp.arange(n_chunks))
    return vals, ids, gt, eq


def eval_tgt_scores_ref(
    x: jax.Array,  # (B, d)
    y: jax.Array,  # (C, d)
    targets: jax.Array,  # (B,) i32 global catalog ids
    *,
    chunk: int = 512,
    id_offset=0,
):
    """Target-column scores extracted from the SAME chunked matmul
    ``eval_topk_ref`` streams (same ``chunk`` ⇒ bitwise-identical column
    values ⇒ exact ``gt``/``eq`` counts). Rows whose target lies outside
    ``y``'s id range contribute 0, so a ``psum`` over catalog shards
    assembles the exact score. → (B,) f32."""
    b, _ = x.shape
    c = y.shape[0]
    chunk = min(chunk, c)
    pad = (-c) % chunk
    yp = jnp.pad(y, ((0, pad), (0, 0)))
    n_chunks = (c + pad) // chunk
    f32 = jnp.float32
    x32 = x.astype(f32)
    tid = targets.astype(jnp.int32)[:, None]

    def body(acc, jc):
        rows = jax.lax.dynamic_slice_in_dim(yp, jc * chunk, chunk, 0)
        s = x32 @ rows.astype(f32).T
        col = id_offset + jc * chunk + jnp.arange(chunk, dtype=jnp.int32)
        hit = jnp.broadcast_to(col[None, :], s.shape) == tid
        return acc + jnp.sum(jnp.where(hit, s, 0.0), axis=-1), None

    acc0 = vary_as(jnp.zeros((b,), f32), x, y, targets, id_offset)
    acc, _ = jax.lax.scan(body, acc0, jnp.arange(n_chunks))
    return acc


def eval_tgt_gather_ref(
    x: jax.Array,  # (B, d)
    y: jax.Array,  # (C, d)
    targets: jax.Array,  # (B,) i32 global catalog ids
    *,
    chunk: int = 512,
    id_offset=0,
):
    """Target-column scores from chunk-SHAPED gather matmuls — the
    pure-jnp twin of ``kernels/eval_fused.eval_tgt_gather`` and the
    single-sweep replacement for :func:`eval_tgt_scores_ref`.

    Each row's target embedding is gathered into a ``(chunk, d)``
    buffer (``ceil(B/chunk)`` of them) and scored with the *same*
    ``(B, d) @ (d, chunk)`` matmul :func:`eval_fused_ref`'s scan runs —
    a gemm's per-element reduction depends on the operand shapes, not
    the column position or the other columns, so the extracted slot is
    bitwise identical to the sweep's target column (the property that
    motivated the deprecated full-sweep ``eval_tgt_scores_ref``) at
    ``O(B·ceil(B/chunk)·chunk·d)`` FLOPs instead of ``O(B·C·d)``.
    Rows whose target lies outside ``y``'s id range contribute 0 (so a
    ``psum`` over catalog shards assembles the exact score). → (B,)
    f32.
    """
    b, d = x.shape
    c = y.shape[0]
    if b == 0:
        return jnp.zeros((0,), jnp.float32)
    chunk = min(chunk, c)
    local = targets.astype(jnp.int32) - id_offset
    owned = jnp.logical_and(local >= 0, local < c)
    rows = jnp.where(
        owned[:, None], jnp.take(y, jnp.clip(local, 0, c - 1), axis=0), 0
    )  # (B, d) — unowned rows zeroed (x · 0 ≡ 0 exactly)
    n_g = -(-b // chunk)
    pad = n_g * chunk - b
    rows_p = jnp.pad(rows, ((0, pad), (0, 0))).reshape(n_g, chunk, d)
    f32 = jnp.float32
    x32 = x.astype(f32)

    def body(_, rg):
        return _, x32 @ rg.astype(f32).T  # (B, chunk) — the sweep shape

    _, ss = jax.lax.scan(body, 0, rows_p)  # (n_g, B, chunk)
    i = jnp.arange(b)
    return ss[i // chunk, i, i % chunk]


def eval_fused_ref(
    x: jax.Array,  # (B, d)
    y: jax.Array,  # (C, d) catalog (or a catalog shard)
    targets: jax.Array,  # (B,) i32 global target ids
    k: int,
    *,
    tgt_scores=None,  # optional (B,) f32 threshold (sharded: psum'd)
    chunk: int = 512,
    c_lo: int = 0,
    c_hi=None,
    id_offset=0,
    logit_softcap=None,
    with_lse: bool = False,
):
    """Single-sweep streaming top-k + rank counts (+ online-LSE) —
    pure-jnp oracle for ``kernels/eval_fused.py`` (and the path used
    inside ``shard_map`` / with a traced ``id_offset``, see
    ``kernels/ops.py``).

    One ``lax.scan`` over ``(chunk, d)`` catalog slices carrying
    ``(topk_vals, topk_ids, gt, eq[, m, s])`` — one matmul per chunk
    where the two-pass :func:`eval_tgt_scores_ref` +
    :func:`eval_topk_ref` pair ran two. The comparison threshold
    defaults to the bitwise-exact :func:`eval_tgt_gather_ref`; the
    target's own column is excluded from ``gt`` and force-counted into
    ``eq`` structurally (a no-op vs plain ``>``/``==`` while the
    threshold is bit-exact, and it pins ``eq ≥ 1`` regardless).
    ``logit_softcap`` applies to the LSE carry only (CE is not
    cap-invariant; ranks are, so they keep raw logits).

    Returns ``(vals, ids, gt, eq, tgt, m, s)`` with ``m``/``s`` None
    when ``with_lse=False``; the first four match the two-pass path
    bit-for-bit, ``lse = m + log s``.
    """
    b, _ = x.shape
    c = y.shape[0]
    if c_hi is None:
        c_hi = id_offset + c
    chunk = min(chunk, c)
    if tgt_scores is None:
        tgt_scores = eval_tgt_gather_ref(
            x, y, targets, chunk=chunk, id_offset=id_offset
        )
    pad = (-c) % chunk
    yp = jnp.pad(y, ((0, pad), (0, 0)))
    n_chunks = (c + pad) // chunk
    f32 = jnp.float32
    x32 = x.astype(f32)
    tgt = tgt_scores.astype(f32)[:, None]
    tid = targets.astype(jnp.int32)[:, None]

    vals0 = jnp.full((b, k), NEG_INF, f32)
    ids0 = jnp.full((b, k), ID_PAD, jnp.int32)
    cnt0 = jnp.zeros((b,), jnp.int32)
    carry0 = (vals0, ids0, cnt0, cnt0)
    if with_lse:
        carry0 += (jnp.full((b,), NEG_INF, f32), jnp.zeros((b,), f32))

    def body(carry, jc):
        if with_lse:
            vals, ids, gt, eq, m, se = carry
        else:
            vals, ids, gt, eq = carry
        rows = jax.lax.dynamic_slice_in_dim(yp, jc * chunk, chunk, 0)
        logits = x32 @ rows.astype(f32).T  # (b, chunk) — THE matmul
        idx = jc * chunk + jnp.arange(chunk, dtype=jnp.int32)
        col = jnp.broadcast_to((id_offset + idx)[None, :], logits.shape)
        valid = jnp.logical_and(
            jnp.broadcast_to((idx < c)[None, :], logits.shape),
            jnp.logical_and(col >= c_lo, col < c_hi),
        )
        s = jnp.where(valid, logits, NEG_INF)
        self_col = col == tid
        gt = gt + jnp.sum(
            jnp.logical_and(s > tgt, ~self_col).astype(jnp.int32), axis=-1
        )
        eq = eq + jnp.sum(
            jnp.logical_or(
                s == tgt, jnp.logical_and(self_col, valid)
            ).astype(jnp.int32),
            axis=-1,
        )
        cat_v = jnp.concatenate([vals, s], axis=-1)
        cat_i = jnp.concatenate([ids, col], axis=-1)
        v, sel = jax.lax.top_k(cat_v, k)
        i = jnp.take_along_axis(cat_i, sel, axis=-1)
        if not with_lse:
            return (v, i, gt, eq), None
        cap = logit_softcap
        lv = jnp.where(
            valid,
            logits if cap is None else cap * jnp.tanh(logits / cap),
            NEG_INF,
        )
        m_new = jnp.maximum(m, jnp.max(lv, axis=-1))
        se = se * jnp.exp(m - m_new) + jnp.sum(
            jnp.where(valid, jnp.exp(lv - m_new[:, None]), 0.0), axis=-1
        )
        return (v, i, gt, eq, m_new, se), None

    carry0 = vary_as(carry0, x, y, targets, tgt_scores, id_offset)
    carry, _ = jax.lax.scan(body, carry0, jnp.arange(n_chunks))
    if with_lse:
        vals, ids, gt, eq, m, se = carry
        return vals, ids, gt, eq, tgt_scores, m, se
    vals, ids, gt, eq = carry
    return vals, ids, gt, eq, tgt_scores, None, None


def linear_ce_loss_ref(
    x: jax.Array,  # (N, d) hidden states
    w: jax.Array,  # (V, d) head table
    targets: jax.Array,  # (N,) i32 vocab ids
    *,
    logit_softcap=None,
    chunk: int = 512,
) -> jax.Array:
    """Chunked streaming linear-CE — pure-jnp oracle for
    ``kernels/linear_sce.py`` (and the path used inside ``shard_map``,
    see ``kernels/ops.py``).

    One ``lax.scan`` over ``(chunk, d)`` vocab slices carrying the online
    logsumexp ``(m, s)`` plus the per-position positive accumulator —
    the target's (capped) logit is plucked from the chunk it streams by
    in, mirroring the kernel's in-tile extraction. ``logit_softcap``
    applies to every logit before it enters either accumulator (CE is
    not cap-invariant). Differentiable through ordinary autodiff (the
    scan's saved residuals make the *backward* memory O(N·V) here —
    oracle only; the kernel recomputes). → (N,) losses in ``x.dtype``.
    """
    n, _ = x.shape
    c = w.shape[0]
    chunk = min(chunk, c)
    pad = (-c) % chunk
    wp = jnp.pad(w, ((0, pad), (0, 0)))
    n_chunks = (c + pad) // chunk
    f32 = jnp.float32
    x32 = x.astype(f32)
    tid = targets.astype(jnp.int32)[:, None]
    cap = logit_softcap

    def body(carry, jc):
        m, s, pos = carry
        rows = jax.lax.dynamic_slice_in_dim(wp, jc * chunk, chunk, 0)
        logits = x32 @ rows.astype(f32).T  # (n, chunk)
        capped = logits if cap is None else cap * jnp.tanh(logits / cap)
        idx = jc * chunk + jnp.arange(chunk, dtype=jnp.int32)
        lv = jnp.where((idx < c)[None, :], capped, NEG_INF)
        pos = pos + jnp.sum(
            jnp.where(jnp.broadcast_to(idx[None, :], lv.shape) == tid, lv, 0.0),
            axis=-1,
        )
        m_new = jnp.maximum(m, jnp.max(lv, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(lv - m_new[:, None]), axis=-1
        )
        return (m_new, s, pos), None

    carry0 = vary_as(
        (jnp.full((n,), NEG_INF, f32), jnp.zeros((n,), f32),
         jnp.zeros((n,), f32)),
        x, w, targets,
    )
    (m, s, pos), _ = jax.lax.scan(body, carry0, jnp.arange(n_chunks))
    return (m + jnp.log(s) - pos).astype(x.dtype)


def fused_lse_ref(x: jax.Array, y: jax.Array) -> jax.Array:
    """Full-catalog logsumexp per position. x: (N, d), y: (C, d) → (N,)."""
    logits = x.astype(jnp.float32) @ y.astype(jnp.float32).T
    return jax.nn.logsumexp(logits, axis=-1).astype(x.dtype)


def fused_ce_loss_ref(x: jax.Array, y: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position full-CE loss. Returns (N,)."""
    lse = fused_lse_ref(x, y)
    pos = jnp.einsum(
        "nd,nd->n",
        x.astype(jnp.float32),
        jnp.take(y, targets, axis=0).astype(jnp.float32),
    )
    return (lse.astype(jnp.float32) - pos).astype(x.dtype)


def causal_attention_ref(q, k, v, scale: float) -> jax.Array:
    """Causal self-attention with the whole ``(B, Hq, L, L)`` float32
    score tensor: ``q (B, L, Hq, dqk)``, ``k (B, L, Hkv, dqk)``, ``v (B,
    L, Hkv, dv)`` → ``(B, L, Hq, dv)``; a key/value head serves ``Hq /
    Hkv`` query heads."""
    f32 = jnp.float32
    seq, g = q.shape[1], q.shape[2] // k.shape[2]
    k = jnp.repeat(k.astype(f32), g, axis=2)
    v = jnp.repeat(v.astype(f32), g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), k) * scale
    s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, NEG_INF)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return out.astype(q.dtype)
