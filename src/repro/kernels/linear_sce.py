"""Fused linear cross-entropy — the full-CE arm of linear-SCE training.

LM training wants ``loss(X @ Wᵀ)`` for ``X`` = (B·T, d) hidden states and
``W`` = (V, d) head table, but at gemma-2 scale the ``(B·T, V)`` logit
tensor is the single biggest allocation of the step. This module computes
the per-position CE loss AND both gradients in streaming passes — the
logit matrix never hits HBM in either direction:

  * ``_fwd_kernel``    — one sweep over vocab tiles carrying the online
    logsumexp ``(m, s)`` (the ``fused_ce``/``eval_fused`` recurrence)
    PLUS a per-position positive accumulator: the target's logit is
    plucked from the tile it streams by in (``col == target`` masking),
    so — unlike ``fused_ce_loss`` — no external gather-einsum and no
    uncapped positive. Emits ``loss = lse − pos`` and ``lse``.
  * ``_bwd_dx_kernel``  — dX = ((p − 1ₜ)·capᕁ·g) @ W, streamed over V.
  * ``_bwd_dw_kernel``  — dW = ((p − 1ₜ)·capᕁ·g)ᵀ @ X, streamed over N
    (grid transposed: position tiles innermost, ``(block_c, d)``
    accumulator carried across them — the ``fused_ce`` dY rule).

The gemma-2 logit softcap ``cap·tanh(logit/cap)`` is applied INSIDE the
tile, before the padded-tail mask (CE is not cap-invariant — the same
rule ``eval_fused`` encodes for its LSE carry; capping NEG_INF would
turn masked columns into ``−cap``). The backward factor is analytic:
``d softcap/d logit = 1 − tanh² = 1 − (capped/cap)²``, so both backward
kernels recompute the capped tile and scale the softmax cotangent by it.

Backward = recomputation: only the per-position ``lse`` is saved, peak
memory is one tile pair + one ``(block, d)`` accumulator — same
flash-style trade as every other kernel in this layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot, pad_to, psum_to, sds

NEG_INF = -1e30


def _capped(logits, logit_softcap):
    if logit_softcap is None:
        return logits
    return logit_softcap * jnp.tanh(logits / logit_softcap)


def _cap_deriv(capped, logit_softcap):
    """d softcap/d logit as a function of the CAPPED value (tanh already
    computed): ``1 − tanh²``. 1.0 when no cap."""
    if logit_softcap is None:
        return 1.0
    t = capped / logit_softcap
    return 1.0 - t * t


def _fwd_kernel(
    tgt_ref,  # (n_t, 1) i32 — padded rows carry -1 (never matches a column)
    x_ref,  # (n_t, d)
    w_ref,  # (c_t, d)
    loss_ref,  # (n_t, 1) f32 out
    lse_ref,  # (n_t, 1) f32 out
    m_scr,  # (n_t, 1) f32
    s_scr,  # (n_t, 1) f32
    pos_scr,  # (n_t, 1) f32
    *,
    n_c_tiles: int,
    c_actual: int,
    block_c: int,
    logit_softcap: float | None,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)
        pos_scr[...] = jnp.zeros_like(pos_scr)

    logits = dot(x_ref[...], w_ref[...].T)
    capped = _capped(logits, logit_softcap)
    col_ids = j * block_c + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    capped = jnp.where(col_ids >= c_actual, NEG_INF, capped)

    # The target's (capped) logit streams by exactly once — accumulate it.
    pos_scr[...] += jnp.sum(
        jnp.where(col_ids == tgt_ref[...], capped, 0.0),
        axis=-1, keepdims=True,
    )

    m_prev, s_prev = m_scr[...], s_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(capped, axis=-1, keepdims=True))
    s_scr[...] = s_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(capped - m_new), axis=-1, keepdims=True
    )
    m_scr[...] = m_new

    @pl.when(j == n_c_tiles - 1)
    def _finalize():
        lse = m_new + jnp.log(s_scr[...])
        lse_ref[...] = lse
        loss_ref[...] = lse - pos_scr[...]


def _softmax_cotangent(x_ref, w_ref, tgt_ref, lse_ref, g_ref, jc, *, c_actual,
                       block_c, logit_softcap):
    """The shared backward tile: ``(p − onehot)·capᕁ·g`` in f32."""
    logits = dot(x_ref[...], w_ref[...].T)
    capped = _capped(logits, logit_softcap)
    col_ids = jc * block_c + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    p = jnp.where(col_ids >= c_actual, 0.0, jnp.exp(capped - lse_ref[...]))
    onehot = (col_ids == tgt_ref[...]).astype(jnp.float32)
    gl = (p - onehot) * _cap_deriv(capped, logit_softcap)
    return gl * g_ref[...].astype(jnp.float32)


def _bwd_dx_kernel(
    tgt_ref,
    lse_ref,
    g_ref,
    x_ref,
    w_ref,
    dx_ref,  # (n_t, d) out
    acc_scr,  # (n_t, d) f32
    *,
    n_c_tiles: int,
    c_actual: int,
    block_c: int,
    logit_softcap: float | None,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    gw = _softmax_cotangent(
        x_ref, w_ref, tgt_ref, lse_ref, g_ref, j,
        c_actual=c_actual, block_c=block_c, logit_softcap=logit_softcap,
    )
    acc_scr[...] += dot(gw.astype(w_ref.dtype), w_ref[...])

    @pl.when(j == n_c_tiles - 1)
    def _finalize():
        dx_ref[...] = acc_scr[...].astype(dx_ref.dtype)


def _bwd_dw_kernel(
    tgt_ref,
    lse_ref,
    g_ref,
    x_ref,
    w_ref,
    dw_ref,  # (c_t, d) out
    acc_scr,  # (c_t, d) f32
    *,
    n_n_tiles: int,
    c_actual: int,
    block_c: int,
    logit_softcap: float | None,
):
    # grid = (n_c_tiles, n_n_tiles): program_id(0) = vocab tile,
    # program_id(1) = position tile (innermost).
    jc = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    gw = _softmax_cotangent(
        x_ref, w_ref, tgt_ref, lse_ref, g_ref, jc,
        c_actual=c_actual, block_c=block_c, logit_softcap=logit_softcap,
    )
    acc_scr[...] += dot(gw.T.astype(x_ref.dtype), x_ref[...])

    @pl.when(i == n_n_tiles - 1)
    def _finalize():
        dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)


def _prep(x, w, targets, block_n, block_c):
    n = x.shape[0]
    c = w.shape[0]
    block_n = min(block_n, n)
    block_c = min(block_c, c)
    xp = pad_to(x, 0, block_n)
    wp = pad_to(w, 0, block_c)
    # Padded positions carry target -1: no column matches, pos stays 0.
    # Per-position vectors travel as (N, 1) columns so their
    # (block_n, 1) blocks meet the TPU tiling rule.
    tp = pad_to(targets.astype(jnp.int32)[:, None], 0, block_n, value=-1)
    return xp, wp, tp, block_n, block_c


def _fwd(x, w, targets, *, logit_softcap, block_n, block_c, interpret):
    n, d = x.shape
    c = w.shape[0]
    xp, wp, tp, block_n, block_c = _prep(x, w, targets, block_n, block_c)
    n_p, c_p = xp.shape[0], wp.shape[0]
    n_n, n_c = n_p // block_n, c_p // block_c

    loss, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, n_c_tiles=n_c, c_actual=c, block_c=block_c,
            logit_softcap=logit_softcap,
        ),
        grid=(n_n, n_c),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            sds((n_p, 1), jnp.float32, tp, xp, wp),
            sds((n_p, 1), jnp.float32, tp, xp, wp),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.float32)] * 3,
        interpret=interpret,
        name="linear_ce_fwd",
    )(tp, xp, wp)
    return loss[:n, 0], lse[:n, 0]


def _bwd(x, w, targets, lse, g, *, logit_softcap, block_n, block_c, interpret):
    n, d = x.shape
    c = w.shape[0]
    xp, wp, tp, block_n, block_c = _prep(x, w, targets, block_n, block_c)
    lp = pad_to(lse[:, None], 0, block_n)
    gp = pad_to(g.astype(jnp.float32)[:, None], 0, block_n)  # 0 on pad
    n_p, c_p = xp.shape[0], wp.shape[0]
    n_n, n_c = n_p // block_n, c_p // block_c

    dx = pl.pallas_call(
        functools.partial(
            _bwd_dx_kernel, n_c_tiles=n_c, c_actual=c, block_c=block_c,
            logit_softcap=logit_softcap,
        ),
        grid=(n_n, n_c),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=sds((n_p, d), x.dtype, xp, wp, lp, gp),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        interpret=interpret,
        name="linear_ce_bwd_dx",
    )(tp, lp, gp, xp, wp)

    dw = pl.pallas_call(
        functools.partial(
            _bwd_dw_kernel, n_n_tiles=n_n, c_actual=c, block_c=block_c,
            logit_softcap=logit_softcap,
        ),
        grid=(n_c, n_n),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_c, d), lambda j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, d), lambda j, i: (j, 0)),
        out_shape=sds((c_p, d), w.dtype, xp, wp, lp, gp),
        scratch_shapes=[pltpu.VMEM((block_c, d), jnp.float32)],
        interpret=interpret,
        name="linear_ce_bwd_dw",
    )(tp, lp, gp, xp, wp)

    return dx[:n], dw[:c]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def linear_ce_loss(
    x,
    w,
    targets,
    logit_softcap: float | None = None,
    block_n: int = 256,
    block_c: int = 512,
    interpret: bool = False,
):
    """Per-position full-vocab CE loss from hidden states + head table.

    ``x``: (N, d), ``w``: (V, d), ``targets``: (N,) i32 → (N,) losses in
    ``x.dtype``. The ``(N, V)`` logit matrix never exists, forward or
    backward; ``logit_softcap`` (gemma-2) is applied inside the tile.
    ``targets`` is a regular (index) argument with a ``None`` cotangent.
    """
    loss, _ = _fwd(
        x, w, targets,
        logit_softcap=logit_softcap, block_n=block_n, block_c=block_c,
        interpret=interpret,
    )
    return loss.astype(x.dtype)


def _vjp_fwd(x, w, targets, logit_softcap, block_n, block_c, interpret):
    loss, lse = _fwd(
        x, w, targets,
        logit_softcap=logit_softcap, block_n=block_n, block_c=block_c,
        interpret=interpret,
    )
    return loss.astype(x.dtype), (x, w, targets, lse)


def _vjp_bwd(logit_softcap, block_n, block_c, interpret, res, g):
    x, w, targets, lse = res
    dx, dw = _bwd(
        x, w, targets, lse, g,
        logit_softcap=logit_softcap, block_n=block_n, block_c=block_c,
        interpret=interpret,
    )
    return psum_to(dx, x), psum_to(dw, w), None


linear_ce_loss.defvjp(_vjp_fwd, _vjp_bwd)
