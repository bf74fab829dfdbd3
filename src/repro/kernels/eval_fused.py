"""Fused single-pass streaming eval scorer — Pallas TPU kernel.

Collapses the evaluation stack's repeated catalog sweeps into ONE. The
two-pass path (``kernels/eval_topk.py``) streams the same
``(B, d) @ (d, C)`` matmul twice — once to extract each row's target
score (``eval_tgt_scores``), once for the rank counts and top-k
(``eval_topk``) — and the LM token-rank protocol added a third V-wide
sweep for the chunked online-LSE NLL (``core.losses.ce_chunked``). The
scoring matmul, not the reduction, dominates eval cost at large
catalogs (RECE, Gusak et al. 2024; Zhelnin et al. 2025), so every
duplicated sweep is pure FLOP/HBM waste. Here one matmul per
``(block_c, d)`` tile feeds **four accumulators**:

  * ``(topk_vals, topk_ids)`` — the ``(block_b, K)`` merge buffer
    (``topk_merge.insert_topk_tile``, dense-``lax.top_k`` tie order);
  * ``(gt, eq)`` — rank counts vs the target score (raw logits: ranks
    are softcap-invariant);
  * an optional f32 online-LSE ``(m, s)`` carry over the *softcapped*
    logits (CE is NOT cap-invariant, so the cap is applied inside the
    tile) — the LM NLL without its own sweep.

Why the target score must be an input (the single-pass obstruction)
-------------------------------------------------------------------
``gt``/``eq`` compare every catalog score against the target score, but
a forward sweep only reveals the target's column when its tile streams
by — comparisons for earlier tiles would need the full prefix score
multiset, which no ``O(B·K)`` carry can hold exactly. An exact single
sweep therefore requires the target score BEFORE tile 0.

The cheap way out is :func:`eval_tgt_gather`: gather each row's target
embedding into a **tile-shaped** ``(block_c, d)`` buffer (row ``r`` of
the buffer = row-block row ``r``'s target) and run the *same*
``(block_b, d) @ (d, block_c)`` ``jnp.dot`` the sweep runs. A gemm's
per-element reduction order depends on the operand shapes, not on the
column position or the other columns' contents (MXU: one systolic
schedule per shape; XLA:CPU: one blocked loop nest per shape), so the
extracted slot is **bitwise identical** to the value the sweep computes
for that target's column — the consistency property that motivated
``eval_tgt_scores``, now at ``O(B·block_c·d)`` FLOPs instead of a full
``O(B·C·d)`` sweep. (A gather-einsum is NOT safe: measured 1-ulp
mismatches on ~15–25% of rows — see KERNELS.md §eval_topk.) The
equality tests pin this bit-for-bit against ``eval_tgt_scores``.

Inside the sweep the target's own column is handled *structurally*
(``col == target`` never counts into ``gt``, always counts into ``eq``
when valid) — identical to the two-pass counts whenever the threshold
is bit-exact (always, by the construction above) and preserving the
``eq ≥ 1`` invariant even if a backend ever broke the same-shape-gemm
assumption.

Grid: ``(B/block_b, C/block_c)``, catalog innermost / sequential so the
VMEM scratch carries across tiles. No backward pass — eval is
inference-only. Peak live elements match ``eval_topk``'s
``B·(block_c + 2K + 2)`` model (+ the ``(m, s)`` pair when the LSE
carry is on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot, pad_to, sds
from repro.kernels.topk_merge import ID_PAD as _ID_PAD
from repro.kernels.topk_merge import buffer_lanes, insert_topk_tile

NEG_INF = -1e30


def _softcap(logits, cap):
    """gemma-2-style ``cap·tanh(logits/cap)`` (None = identity) —
    duplicated from ``core.sce.apply_softcap`` to keep the kernel layer
    import-free of ``repro.core``."""
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def _tgt_gather_kernel(
    tid_ref,  # (block_b, 1) i32 — local target row, -1 if not owned
    x_ref,  # (block_b, d)
    yg_ref,  # (block_c, d) — row r holds row r's target embedding
    out_ref,  # (block_b, 1) f32 out
    *,
    block_b: int,
):
    # The SAME dot the sweep kernel runs — same (block_b, d, block_c)
    # shape ⇒ same per-element reduction ⇒ bitwise-identical scores.
    logits = dot(x_ref[...], yg_ref[...].T)
    row = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    hit = row == col  # row r's target sits in gather-tile row r
    owned = tid_ref[...] >= 0
    out_ref[...] = jnp.sum(
        jnp.where(jnp.logical_and(hit, owned), logits, 0.0),
        axis=-1, keepdims=True,
    )


def _fused_kernel(
    tgt_ref,  # (block_b, 1) f32 target scores (the comparison threshold)
    tid_ref,  # (block_b, 1) i32 local target ids (self-column rule)
    valid_ref,  # (1, block_c) i32 — 1 inside the id window, 0 elsewhere
    x_ref,  # (block_b, d)
    y_ref,  # (block_c, d)
    *refs,  # outputs then scratch — see `with_lse` unpacking below
    k: int,
    n_c_tiles: int,
    block_c: int,
    logit_softcap,
    with_lse: bool,
):
    if with_lse:
        (vals_ref, ids_ref, gt_ref, eq_ref, m_ref, s_ref,
         vals_scr, ids_scr, gt_scr, eq_scr, m_scr, s_scr) = refs
    else:
        (vals_ref, ids_ref, gt_ref, eq_ref,
         vals_scr, ids_scr, gt_scr, eq_scr) = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_scr[...] = jnp.full_like(vals_scr, NEG_INF)
        ids_scr[...] = jnp.full_like(ids_scr, _ID_PAD)
        gt_scr[...] = jnp.zeros_like(gt_scr)
        eq_scr[...] = jnp.zeros_like(eq_scr)
        if with_lse:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            s_scr[...] = jnp.zeros_like(s_scr)

    # THE one matmul per tile — every accumulator below reads it.
    logits = dot(x_ref[...], y_ref[...].T)
    col = j * block_c + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1
    )
    # Columns outside the id window and the padded tail are masked.
    valid = valid_ref[...] > 0
    s = jnp.where(valid, logits, NEG_INF)

    # Rank counts vs the (bitwise-exact) threshold. The target's own
    # column is excluded from gt and force-counted into eq structurally
    # — a no-op vs plain (>, ==) when the threshold is exact, but it
    # pins eq ≥ 1 independent of any backend's gemm determinism.
    tgt = tgt_ref[...]  # (block_b, 1)
    self_col = col == tid_ref[...]
    gt_scr[...] += jnp.sum(
        jnp.logical_and(s > tgt, jnp.logical_not(self_col)).astype(
            jnp.int32
        ),
        axis=-1, keepdims=True,
    )
    eq_scr[...] += jnp.sum(
        jnp.logical_or(
            s == tgt, jnp.logical_and(self_col, valid)
        ).astype(jnp.int32),
        axis=-1, keepdims=True,
    )

    # The shared insertion merge — raw logits, dense tie rule.
    vals_scr[...], ids_scr[...] = insert_topk_tile(
        vals_scr[...], ids_scr[...], s, j * block_c, k
    )

    if with_lse:
        # Online logsumexp over the SOFTCAPPED logits (CE is not
        # cap-invariant; ranks above keep the raw scores). Invalid
        # columns contribute exactly 0 via the explicit where — never
        # relying on exp(NEG_INF − NEG_INF) when a whole tile is masked.
        lv = jnp.where(valid, _softcap(logits, logit_softcap), NEG_INF)
        m_new = jnp.maximum(
            m_scr[...], jnp.max(lv, axis=-1, keepdims=True)
        )
        s_scr[...] = s_scr[...] * jnp.exp(m_scr[...] - m_new) + jnp.sum(
            jnp.where(valid, jnp.exp(lv - m_new), 0.0),
            axis=-1, keepdims=True,
        )
        m_scr[...] = m_new

    @pl.when(j == n_c_tiles - 1)
    def _finalize():
        vals_ref[...] = vals_scr[:, :k].astype(vals_ref.dtype)
        ids_ref[...] = ids_scr[:, :k]
        gt_ref[...] = gt_scr[...]
        eq_ref[...] = eq_scr[...]
        if with_lse:
            m_ref[...] = m_scr[...]
            s_ref[...] = s_scr[...]


def eval_tgt_gather(
    x,
    y,
    targets,
    *,
    block_b: int = 128,
    block_c: int = 512,
    id_offset: int = 0,
    interpret: bool = False,
):
    """Each row's target-column score from a tile-SHAPED gather matmul —
    bitwise identical to the column :func:`eval_fused`'s sweep computes
    (same ``(block_b, d) @ (d, block_c)`` ``jnp.dot``; see the module
    docstring for the shape-determinism argument), at
    ``O(B·block_c·d)`` FLOPs instead of a catalog sweep.

    Parameters
    ----------
    x : (B, d) user/query states.
    y : (C, d) catalog table (or shard; ``id_offset`` = first row's
        global id).
    targets : (B,) i32 global catalog id of each row's held-out item.
        Rows whose target falls outside ``y``'s id range contribute 0
        (so a ``psum`` over catalog shards assembles the exact value —
        the same contract as the deprecated ``eval_tgt_scores``).
    block_b, block_c : MUST match the sweep call's blocks (that is what
        makes the extraction bitwise-consistent); ``block_b`` is
        clamped to ``block_c`` so every row block fits one gather tile.

    Returns
    -------
    (B,) f32 target scores.
    """
    n, d = x.shape
    c = y.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.float32)
    block_c = min(block_c, c)
    block_b = min(block_b, n, block_c)

    local = targets.astype(jnp.int32) - id_offset
    owned = jnp.logical_and(local >= 0, local < c)
    rows = jnp.where(
        owned[:, None], jnp.take(y, jnp.clip(local, 0, c - 1), axis=0), 0
    )  # (B, d) — unowned rows zeroed (x · 0 ≡ 0 exactly)

    xp = pad_to(x, 0, block_b)
    # Per-row vectors travel as (B, 1) columns: their (block_b, 1) blocks
    # meet the TPU tiling rule and broadcast against the logit tile.
    tidp = pad_to(
        jnp.where(owned, local, -1).astype(jnp.int32)[:, None], 0, block_b,
        value=-1,
    )
    n_p = xp.shape[0]
    n_b = n_p // block_b
    # (n_b, block_b, d) → column-pad each row block to a full
    # (block_c, d) gather tile.
    rows_p = pad_to(rows, 0, block_b).reshape(n_b, block_b, d)
    rows_p = pad_to(rows_p, 1, block_c).reshape(n_b * block_c, d)

    out = pl.pallas_call(
        functools.partial(_tgt_gather_kernel, block_b=block_b),
        grid=(n_b,),
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=sds((n_p, 1), jnp.float32, tidp, xp, rows_p),
        interpret=interpret,
        name="eval_tgt_gather",
    )(tidp, xp, rows_p)
    return out[:n, 0]


def eval_fused(
    x,
    y,
    targets,
    k: int,
    *,
    tgt_scores=None,
    block_b: int = 128,
    block_c: int = 512,
    c_lo: int = 0,
    c_hi: int | None = None,
    id_offset: int = 0,
    logit_softcap: float | None = None,
    with_lse: bool = False,
    interpret: bool = False,
):
    """Single-sweep streaming top-k + rank counts (+ online-LSE) over
    the full catalog — one matmul per tile where the two-pass
    ``eval_tgt_scores`` + ``eval_topk`` pair ran two (and the LM NLL a
    third).

    Parameters
    ----------
    x : (B, d) user/query states.
    y : (C, d) catalog embedding table (or a shard of it).
    targets : (B,) i32 global target ids.
    k : number of top items to keep per row.
    tgt_scores : optional (B,) f32 comparison threshold. Default: the
        bitwise-exact :func:`eval_tgt_gather` over this ``y``. Sharded
        callers pass the ``psum`` of per-shard gathers so every shard
        compares against the full-catalog target score.
    block_b, block_c : VMEM tile sizes.
    c_lo, c_hi : half-open global-id validity window (defaults to
        ``[0, id_offset + C)``); invalid columns are excluded from the
        top-k, the rank counts AND the LSE.
    logit_softcap : optional gemma-2 final-logit cap, applied to the
        LSE carry *inside the tile* (ranks/top-k keep raw logits —
        the cap is monotone, CE is not cap-invariant).
    with_lse : carry the f32 online-LSE ``(m, s)`` pair (the LM NLL
        ridealong); off for seqrec, where nothing consumes it.

    Returns
    -------
    (vals, ids, gt, eq, tgt, m, s) :
        ``vals``/``ids``/``gt``/``eq`` exactly as the two-pass
        ``eval_topk`` (bit-for-bit, tie order included); ``tgt`` the
        (B,) threshold actually compared against; ``m``/``s`` the (B,)
        online-LSE carry (``lse = m + log s``) or ``None`` when
        ``with_lse=False``.
    """
    n, d = x.shape
    c = y.shape[0]
    if c_hi is None:
        c_hi = id_offset + c
    if n == 0:  # fully-filtered eval batch — mirror the ref's empties
        z = jnp.zeros((0,), jnp.float32)
        return (
            jnp.zeros((0, k), jnp.float32),
            jnp.zeros((0, k), jnp.int32),
            jnp.zeros((0,), jnp.int32),
            jnp.zeros((0,), jnp.int32),
            z,
            z if with_lse else None,
            z if with_lse else None,
        )
    block_c = min(block_c, c)
    block_b = min(block_b, n, block_c)

    if tgt_scores is None:
        tgt_scores = eval_tgt_gather(
            x, y, targets,
            block_b=block_b, block_c=block_c,
            id_offset=id_offset, interpret=interpret,
        )

    # The kernel works in local ids: the window becomes a (1, C) validity
    # row (0 on the padded tail), targets shift by the offset, and real
    # result ids shift back afterwards. The offset may be traced.
    gids = id_offset + jnp.arange(c, dtype=jnp.int32)
    valid = jnp.logical_and(gids >= c_lo, gids < c_hi).astype(jnp.int32)
    xp = pad_to(x, 0, block_b)
    yp = pad_to(y, 0, block_c)
    vp = pad_to(valid[None, :], 1, block_c)
    tp = pad_to(tgt_scores.astype(jnp.float32)[:, None], 0, block_b)
    tidp = pad_to(
        (targets.astype(jnp.int32) - id_offset)[:, None], 0, block_b,
        value=-1,
    )
    n_p, c_p = xp.shape[0], yp.shape[0]
    n_b, n_c = n_p // block_b, c_p // block_c

    kernel = functools.partial(
        _fused_kernel,
        k=k,
        n_c_tiles=n_c,
        block_c=block_c,
        logit_softcap=logit_softcap,
        with_lse=with_lse,
    )
    operands = (tp, tidp, vp, xp, yp)
    col_spec = pl.BlockSpec((block_b, 1), lambda i, j: (i, 0))
    row_dtypes = [jnp.int32, jnp.int32] + [jnp.float32] * (2 * with_lse)
    out_specs = [
        pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
        pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
    ] + [col_spec] * len(row_dtypes)
    out_shape = [
        sds((n_p, k), jnp.float32, *operands),
        sds((n_p, k), jnp.int32, *operands),
    ] + [sds((n_p, 1), dt, *operands) for dt in row_dtypes]
    scratch = [
        pltpu.VMEM((block_b, buffer_lanes(k)), jnp.float32),
        pltpu.VMEM((block_b, buffer_lanes(k)), jnp.int32),
    ] + [pltpu.VMEM((block_b, 1), dt) for dt in row_dtypes]

    outs = pl.pallas_call(
        kernel,
        grid=(n_b, n_c),
        in_specs=[
            col_spec,
            col_spec,
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="eval_fused",
    )(*operands)
    vals, ids = outs[0][:n], outs[1][:n]
    ids = jnp.where(ids == _ID_PAD, ids, ids + id_offset)
    gt, eq = outs[2][:n, 0], outs[3][:n, 0]
    m = outs[4][:n, 0] if with_lse else None
    s = outs[5][:n, 0] if with_lse else None
    return vals, ids, gt, eq, tgt_scores, m, s
