"""Fused in-bucket SCE loss — Pallas TPU kernel.

Computes Algorithm 1 lines 12–15 (bucket logits → positive-collision mask →
per-position CE) WITHOUT materializing the ``(n_b, b_x, b_y)`` bucket-logit
tensor. ``b_y`` is streamed through VMEM in tiles with an online logsumexp
(flash-attention-style recurrence), so peak loss memory drops from
``O(n_b·b_x·b_y)`` (the paper's GPU implementation) to ``O(n_b·b_x)`` plus
one ``(block_bx × d)`` / ``(block_by × d)`` tile pair — the TPU-native
extension of the paper's own memory argument.

Numerical trick: the positive logit is folded into the running (max, sumexp)
accumulator at tile 0 (``m ← pos, s ← 1``), which keeps every ``exp``
argument ≤ 0 and avoids the -inf-minus--inf corner entirely.

Grid: ``(n_b, b_x/block_bx, b_y/block_by)`` — the last (``b_y``) dimension is
innermost/sequential on TPU, so the VMEM scratch accumulators carry across
``b_y`` tiles. Backward = two streaming kernels (one per operand) that
recompute tile logits from the saved per-position logsumexp.

All matmuls run on the MXU via ``common.dot`` (float32 result, full
float32 precision for float32 operands);
block sizes default to multiples of 128 (MXU lane alignment).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot, pad_to, psum_to, sds
from repro.kernels.linear_sce import _cap_deriv, _capped

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _fwd_kernel(
    tgt_ref,  # (1, bx_t) int32
    cand_ref,  # (1, by_t) int32
    pos_ref,  # (1, bx_t)
    x_ref,  # (1, bx_t, d)
    y_ref,  # (1, by_t, d)
    loss_ref,  # (1, bx_t) out
    lse_ref,  # (1, bx_t) out
    m_scr,  # (bx_t,) f32 scratch — running max
    s_scr,  # (bx_t,) f32 scratch — running sumexp
    *,
    n_by_tiles: int,
    by_actual: int,
    block_by: int,
    logit_softcap: float | None,
):
    j = pl.program_id(2)
    pos = pos_ref[0].astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        # Fold the positive into the accumulator: m = pos, s = exp(pos-pos).
        m_scr[...] = pos
        s_scr[...] = jnp.ones_like(pos)

    x = x_ref[0]
    y = y_ref[0]
    logits = dot(x, y.T)
    # Softcap INSIDE the tile, before the invalid mask (CE is not
    # cap-invariant); the folded positive arrives pre-capped.
    logits = _capped(logits, logit_softcap)

    # Mask (a) candidates that ARE the positive class (not negatives),
    # (b) candidates with a negative = invalid id (padding, or rows owned
    # by another catalog shard in the distributed ids-only exact mode),
    # and (c) padded tail columns beyond the true b_y.
    col_ids = j * block_by + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1
    )
    collide = cand_ref[0][None, :] == tgt_ref[0][:, None]
    invalid = jnp.logical_or(
        jnp.logical_or(collide, cand_ref[0][None, :] < 0),
        col_ids >= by_actual,
    )
    logits = jnp.where(invalid, NEG_INF, logits)

    m_prev = m_scr[...]
    s_prev = s_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    s_new = s_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new[:, None]), axis=-1
    )
    m_scr[...] = m_new
    s_scr[...] = s_new

    @pl.when(j == n_by_tiles - 1)
    def _finalize():
        lse = m_new + jnp.log(s_new)
        lse_ref[0] = lse.astype(lse_ref.dtype)
        loss_ref[0] = (lse - pos).astype(loss_ref.dtype)


# ---------------------------------------------------------------------------
# Forward (partial-LSE variant): logsumexp over in-bucket negatives ONLY —
# the building block of the distributed "union" mode, whose cross-shard
# merge is a logsumexp over per-shard partial LSEs. No positive folded;
# the accumulator starts at (-inf, 0) like fused_ce.
# ---------------------------------------------------------------------------
def _fwd_plse_kernel(
    tgt_ref,  # (1, bx_t) int32
    cand_ref,  # (1, by_t) int32
    x_ref,  # (1, bx_t, d)
    y_ref,  # (1, by_t, d)
    lse_ref,  # (1, bx_t) out
    m_scr,
    s_scr,
    *,
    n_by_tiles: int,
    by_actual: int,
    block_by: int,
    logit_softcap: float | None,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0]
    y = y_ref[0]
    logits = dot(x, y.T)
    logits = _capped(logits, logit_softcap)
    col_ids = j * block_by + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1
    )
    collide = cand_ref[0][None, :] == tgt_ref[0][:, None]
    invalid = jnp.logical_or(
        jnp.logical_or(collide, cand_ref[0][None, :] < 0),
        col_ids >= by_actual,
    )
    logits = jnp.where(invalid, NEG_INF, logits)

    m_prev, s_prev = m_scr[...], s_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    s_scr[...] = s_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new[:, None]), axis=-1
    )
    m_scr[...] = m_new

    @pl.when(j == n_by_tiles - 1)
    def _finalize():
        lse_ref[0] = (
            m_new + jnp.log(jnp.maximum(s_scr[...], 1e-30))
        ).astype(lse_ref.dtype)


# ---------------------------------------------------------------------------
# Backward: dX (and implicitly d_pos via jnp outside) — stream over b_y
# ---------------------------------------------------------------------------
def _bwd_dx_kernel(
    tgt_ref,
    cand_ref,
    lse_ref,  # (1, bx_t)
    g_ref,  # (1, bx_t) upstream cotangent
    x_ref,  # (1, bx_t, d)
    y_ref,  # (1, by_t, d)
    dx_ref,  # (1, bx_t, d) out
    acc_scr,  # (bx_t, d) f32
    *,
    n_by_tiles: int,
    by_actual: int,
    block_by: int,
    logit_softcap: float | None,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]
    y = y_ref[0]
    logits = dot(x, y.T)
    capped = _capped(logits, logit_softcap)
    col_ids = j * block_by + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1
    )
    collide = cand_ref[0][None, :] == tgt_ref[0][:, None]
    invalid = jnp.logical_or(
        jnp.logical_or(collide, cand_ref[0][None, :] < 0),
        col_ids >= by_actual,
    )
    p = jnp.where(invalid, 0.0, jnp.exp(capped - lse_ref[0][:, None]))
    gw = p * _cap_deriv(capped, logit_softcap)  # dL/dlogit tile
    gw = gw * g_ref[0][:, None].astype(jnp.float32)
    acc_scr[...] += dot(gw.astype(y.dtype), y)

    @pl.when(j == n_by_tiles - 1)
    def _finalize():
        dx_ref[0] = acc_scr[...].astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# Backward: dY — stream over b_x (grid transposed so the scratch carries
# across b_x tiles for one fixed b_y tile)
# ---------------------------------------------------------------------------
def _bwd_dy_kernel(
    tgt_ref,
    cand_ref,
    lse_ref,
    g_ref,
    x_ref,
    y_ref,
    dy_ref,  # (1, by_t, d) out
    acc_scr,  # (by_t, d) f32
    *,
    n_bx_tiles: int,
    by_actual: int,
    block_by: int,
    logit_softcap: float | None,
):
    # grid = (n_b, n_by_tiles, n_bx_tiles): program_id(1) = b_y tile,
    # program_id(2) = b_x tile (innermost).
    jy = pl.program_id(1)
    ix = pl.program_id(2)

    @pl.when(ix == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]
    y = y_ref[0]
    logits = dot(x, y.T)
    capped = _capped(logits, logit_softcap)
    col_ids = jy * block_by + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1
    )
    collide = cand_ref[0][None, :] == tgt_ref[0][:, None]
    invalid = jnp.logical_or(
        jnp.logical_or(collide, cand_ref[0][None, :] < 0),
        col_ids >= by_actual,
    )
    p = jnp.where(invalid, 0.0, jnp.exp(capped - lse_ref[0][:, None]))
    gw = p * _cap_deriv(capped, logit_softcap)
    gw = gw * g_ref[0][:, None].astype(jnp.float32)
    acc_scr[...] += dot(gw.T.astype(x.dtype), x)

    @pl.when(ix == n_bx_tiles - 1)
    def _finalize():
        dy_ref[0] = acc_scr[...].astype(dy_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------
def _fwd(x_b, y_b, tgt_b, cand_ids, pos_logit, *, block_bx, block_by,
         interpret, logit_softcap=None):
    n_b, b_x, d = x_b.shape
    b_y = y_b.shape[1]
    block_bx = min(block_bx, b_x)
    block_by = min(block_by, b_y)

    xp = pad_to(x_b, 1, block_bx)
    yp = pad_to(y_b, 1, block_by)
    # Padded targets = -2 and padded candidates = -1 never collide.
    tp = pad_to(tgt_b, 1, block_bx, value=-2)
    cp = pad_to(cand_ids, 1, block_by, value=-1)
    pp = pad_to(pos_logit, 1, block_bx)
    bx_p, by_p = xp.shape[1], yp.shape[1]
    n_bx, n_by = bx_p // block_bx, by_p // block_by

    kernel = functools.partial(
        _fwd_kernel, n_by_tiles=n_by, by_actual=b_y, block_by=block_by,
        logit_softcap=logit_softcap,
    )
    loss, lse = pl.pallas_call(
        kernel,
        grid=(n_b, n_bx, n_by),
        in_specs=[
            pl.BlockSpec((1, block_bx), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, block_by), lambda b, i, j: (b, j)),
            pl.BlockSpec((1, block_bx), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, block_bx, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_by, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_bx), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, block_bx), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            sds((n_b, bx_p), pos_logit.dtype, xp, yp, tp, cp, pp),
            sds((n_b, bx_p), jnp.float32, xp, yp, tp, cp, pp),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_bx,), jnp.float32),
            pltpu.VMEM((block_bx,), jnp.float32),
        ],
        interpret=interpret,
    )(tp, cp, pp, xp, yp)
    return loss[:, :b_x], lse[:, :b_x]


def _bwd(x_b, y_b, tgt_b, cand_ids, lse, g, *, block_bx, block_by,
         interpret, logit_softcap=None):
    n_b, b_x, d = x_b.shape
    b_y = y_b.shape[1]
    block_bx = min(block_bx, b_x)
    block_by = min(block_by, b_y)

    xp = pad_to(x_b, 1, block_bx)
    yp = pad_to(y_b, 1, block_by)
    tp = pad_to(tgt_b, 1, block_bx, value=-2)
    cp = pad_to(cand_ids, 1, block_by, value=-1)
    lp = pad_to(lse, 1, block_bx)
    gp = pad_to(g, 1, block_bx)  # zero cotangent on padded rows
    bx_p, by_p = xp.shape[1], yp.shape[1]
    n_bx, n_by = bx_p // block_bx, by_p // block_by

    dx = pl.pallas_call(
        functools.partial(
            _bwd_dx_kernel, n_by_tiles=n_by, by_actual=b_y,
            block_by=block_by, logit_softcap=logit_softcap,
        ),
        grid=(n_b, n_bx, n_by),
        in_specs=[
            pl.BlockSpec((1, block_bx), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, block_by), lambda b, i, j: (b, j)),
            pl.BlockSpec((1, block_bx), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, block_bx), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, block_bx, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_by, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_bx, d), lambda b, i, j: (b, i, 0)),
        out_shape=sds((n_b, bx_p, d), x_b.dtype, xp, yp, tp, cp, lp, gp),
        scratch_shapes=[pltpu.VMEM((block_bx, d), jnp.float32)],
        interpret=interpret,
    )(tp, cp, lp, gp, xp, yp)

    dy = pl.pallas_call(
        functools.partial(
            _bwd_dy_kernel, n_bx_tiles=n_bx, by_actual=b_y,
            block_by=block_by, logit_softcap=logit_softcap,
        ),
        grid=(n_b, n_by, n_bx),
        in_specs=[
            pl.BlockSpec((1, block_bx), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, block_by), lambda b, j, i: (b, j)),
            pl.BlockSpec((1, block_bx), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, block_bx), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, block_bx, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_by, d), lambda b, j, i: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_by, d), lambda b, j, i: (b, j, 0)),
        out_shape=sds((n_b, by_p, d), y_b.dtype, xp, yp, tp, cp, lp, gp),
        scratch_shapes=[pltpu.VMEM((block_by, d), jnp.float32)],
        interpret=interpret,
    )(tp, cp, lp, gp, xp, yp)

    return dx[:, :b_x], dy[:, :b_y]


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def sce_bucket_loss(
    x_b,
    y_b,
    tgt_b,
    cand_ids,
    pos_logit,
    block_bx: int = 128,
    block_by: int = 256,
    interpret: bool = False,
    logit_softcap: float | None = None,
):
    """Fused in-bucket SCE losses: ``(n_b, b_x)`` per-(bucket, position) CE.

    Matches ``repro.kernels.ref.sce_bucket_loss_ref`` exactly (same masking
    semantics); never materializes the ``(n_b, b_x, b_y)`` logits.
    ``logit_softcap`` caps the negatives inside the tile; ``pos_logit``
    must arrive already capped.
    """
    loss, _ = _fwd(
        x_b, y_b, tgt_b, cand_ids, pos_logit,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        logit_softcap=logit_softcap,
    )
    return loss


def _vjp_fwd(x_b, y_b, tgt_b, cand_ids, pos_logit, block_bx, block_by,
             interpret, logit_softcap):
    loss, lse = _fwd(
        x_b, y_b, tgt_b, cand_ids, pos_logit,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        logit_softcap=logit_softcap,
    )
    return loss, (x_b, y_b, tgt_b, cand_ids, pos_logit, lse)


def _vjp_bwd(block_bx, block_by, interpret, logit_softcap, res, g):
    x_b, y_b, tgt_b, cand_ids, pos_logit, lse = res
    dx, dy = _bwd(
        x_b, y_b, tgt_b, cand_ids, lse, g,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        logit_softcap=logit_softcap,
    )
    # d loss / d pos = (softmax prob of the positive) - 1, times upstream g.
    p_pos = jnp.exp(pos_logit.astype(jnp.float32) - lse)
    d_pos = ((p_pos - 1.0) * g.astype(jnp.float32)).astype(pos_logit.dtype)
    return (psum_to(dx, x_b), psum_to(dy, y_b), None, None,
            psum_to(d_pos, pos_logit))


sce_bucket_loss.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# Public partial-LSE op (union-mode building block) with custom VJP.
# d plse / d logits = softmax over the masked in-bucket negatives — the
# SAME streaming backward kernels as the loss op (they only read lse).
# ---------------------------------------------------------------------------
def _fwd_plse(x_b, y_b, tgt_b, cand_ids, *, block_bx, block_by, interpret,
              logit_softcap=None):
    n_b, b_x, d = x_b.shape
    b_y = y_b.shape[1]
    block_bx = min(block_bx, b_x)
    block_by = min(block_by, b_y)
    xp = pad_to(x_b, 1, block_bx)
    yp = pad_to(y_b, 1, block_by)
    tp = pad_to(tgt_b, 1, block_bx, value=-2)
    cp = pad_to(cand_ids, 1, block_by, value=-1)
    bx_p, by_p = xp.shape[1], yp.shape[1]
    n_bx, n_by = bx_p // block_bx, by_p // block_by

    lse = pl.pallas_call(
        functools.partial(
            _fwd_plse_kernel, n_by_tiles=n_by, by_actual=b_y,
            block_by=block_by, logit_softcap=logit_softcap,
        ),
        grid=(n_b, n_bx, n_by),
        in_specs=[
            pl.BlockSpec((1, block_bx), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, block_by), lambda b, i, j: (b, j)),
            pl.BlockSpec((1, block_bx, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_by, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_bx), lambda b, i, j: (b, i)),
        out_shape=sds((n_b, bx_p), jnp.float32, xp, yp, tp, cp),
        scratch_shapes=[
            pltpu.VMEM((block_bx,), jnp.float32),
            pltpu.VMEM((block_bx,), jnp.float32),
        ],
        interpret=interpret,
    )(tp, cp, xp, yp)
    return lse[:, :b_x]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def sce_bucket_plse(
    x_b,
    y_b,
    tgt_b,
    cand_ids,
    block_bx: int = 128,
    block_by: int = 256,
    interpret: bool = False,
    logit_softcap: float | None = None,
):
    """Per-(bucket, position) partial logsumexp over the in-bucket
    negatives (collision-masked; no positive term) — (n_b, b_x) f32.
    Matches ``ref.sce_bucket_plse_ref``; ``logit_softcap`` caps inside
    the tile."""
    return _fwd_plse(
        x_b, y_b, tgt_b, cand_ids,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        logit_softcap=logit_softcap,
    )


def _plse_vjp_fwd(x_b, y_b, tgt_b, cand_ids, block_bx, block_by, interpret,
                  logit_softcap):
    lse = _fwd_plse(
        x_b, y_b, tgt_b, cand_ids,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        logit_softcap=logit_softcap,
    )
    return lse, (x_b, y_b, tgt_b, cand_ids, lse)


def _plse_vjp_bwd(block_bx, block_by, interpret, logit_softcap, res, g):
    x_b, y_b, tgt_b, cand_ids, lse = res
    dx, dy = _bwd(
        x_b, y_b, tgt_b, cand_ids, lse, g,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        logit_softcap=logit_softcap,
    )
    return psum_to(dx, x_b), psum_to(dy, y_b), None, None


sce_bucket_plse.defvjp(_plse_vjp_fwd, _plse_vjp_bwd)
