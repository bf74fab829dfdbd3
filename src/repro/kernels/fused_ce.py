"""Fused full-vocabulary cross-entropy — Pallas TPU kernel.

The honest TPU baseline for the paper's comparison: full CE whose
``(N, C)`` logit tensor is never materialized. Catalog tiles are streamed
through VMEM with an online logsumexp; the backward pass recomputes tile
logits from the saved per-position logsumexp (so peak memory is
``O(N + C)`` + one tile pair, instead of ``O(N·C)``).

This is the "cut cross-entropy" idea adapted to the TPU memory hierarchy
(HBM → VMEM tiles → MXU matmuls), and makes the CE-vs-SCE comparison a
FLOPs comparison rather than an artifact of materialization: SCE still wins
``N·C / (n_b·b_x·b_y)`` on loss FLOPs.

Kernels:
  * ``_lse_kernel``     — forward: per-position logsumexp over catalog tiles.
  * ``_bwd_dx_kernel``  — dX = (softmax row) @ Y, streamed over C.
  * ``_bwd_dy_kernel``  — dY = (softmax col)ᵀ @ X, streamed over N.

The positive-logit term of the CE loss (a cheap ``(N, d)`` gather-einsum)
lives outside the kernel; its gradient flows through ordinary JAX autodiff.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot, pad_to, psum_to, sds

NEG_INF = -1e30


def _lse_kernel(
    x_ref,  # (n_t, d)
    y_ref,  # (c_t, d)
    lse_ref,  # (n_t,) out
    m_scr,  # (n_t,) f32
    s_scr,  # (n_t,) f32
    *,
    n_c_tiles: int,
    c_actual: int,
    block_c: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)

    logits = dot(x_ref[...], y_ref[...].T)
    col_ids = j * block_c + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col_ids >= c_actual, NEG_INF, logits)

    m_prev, s_prev = m_scr[...], s_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    # s_prev is 0 at init, so the (possibly exp(0)=1) rescale is harmless.
    s_scr[...] = s_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new[:, None]), axis=-1
    )
    m_scr[...] = m_new

    @pl.when(j == n_c_tiles - 1)
    def _finalize():
        lse_ref[...] = (m_new + jnp.log(s_scr[...])).astype(lse_ref.dtype)


def _bwd_dx_kernel(
    lse_ref,  # (n_t,)
    g_ref,  # (n_t,)
    x_ref,  # (n_t, d)
    y_ref,  # (c_t, d)
    dx_ref,  # (n_t, d) out
    acc_scr,  # (n_t, d) f32
    *,
    n_c_tiles: int,
    c_actual: int,
    block_c: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    logits = dot(x_ref[...], y_ref[...].T)
    col_ids = j * block_c + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    p = jnp.where(
        col_ids >= c_actual, 0.0, jnp.exp(logits - lse_ref[...][:, None])
    )
    gw = p * g_ref[...][:, None].astype(jnp.float32)
    acc_scr[...] += dot(gw.astype(y_ref.dtype), y_ref[...])

    @pl.when(j == n_c_tiles - 1)
    def _finalize():
        dx_ref[...] = acc_scr[...].astype(dx_ref.dtype)


def _bwd_dy_kernel(
    lse_ref,
    g_ref,
    x_ref,
    y_ref,
    dy_ref,  # (c_t, d) out
    acc_scr,  # (c_t, d) f32
    *,
    n_n_tiles: int,
    c_actual: int,
    block_c: int,
):
    # grid = (n_c_tiles, n_n_tiles): program_id(0) = catalog tile,
    # program_id(1) = position tile (innermost).
    jc = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    logits = dot(x_ref[...], y_ref[...].T)
    col_ids = jc * block_c + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    p = jnp.where(
        col_ids >= c_actual, 0.0, jnp.exp(logits - lse_ref[...][:, None])
    )
    gw = p * g_ref[...][:, None].astype(jnp.float32)
    acc_scr[...] += dot(gw.T.astype(x_ref.dtype), x_ref[...])

    @pl.when(i == n_n_tiles - 1)
    def _finalize():
        dy_ref[...] = acc_scr[...].astype(dy_ref.dtype)


def _fwd(x, y, *, block_n, block_c, interpret):
    n, d = x.shape
    c = y.shape[0]
    block_n = min(block_n, n)
    block_c = min(block_c, c)
    xp = pad_to(x, 0, block_n)
    yp = pad_to(y, 0, block_c)
    n_p, c_p = xp.shape[0], yp.shape[0]
    n_n, n_c = n_p // block_n, c_p // block_c

    lse = pl.pallas_call(
        functools.partial(
            _lse_kernel, n_c_tiles=n_c, c_actual=c, block_c=block_c
        ),
        grid=(n_n, n_c),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i, j: (i,)),
        out_shape=sds((n_p,), jnp.float32, xp, yp),
        scratch_shapes=[
            pltpu.VMEM((block_n,), jnp.float32),
            pltpu.VMEM((block_n,), jnp.float32),
        ],
        interpret=interpret,
    )(xp, yp)
    return lse[:n]


def _bwd(x, y, lse, g, *, block_n, block_c, interpret):
    n, d = x.shape
    c = y.shape[0]
    block_n = min(block_n, n)
    block_c = min(block_c, c)
    xp = pad_to(x, 0, block_n)
    yp = pad_to(y, 0, block_c)
    lp = pad_to(lse, 0, block_n)
    gp = pad_to(g, 0, block_n)  # zero cotangent on padded rows
    n_p, c_p = xp.shape[0], yp.shape[0]
    n_n, n_c = n_p // block_n, c_p // block_c

    dx = pl.pallas_call(
        functools.partial(
            _bwd_dx_kernel, n_c_tiles=n_c, c_actual=c, block_c=block_c
        ),
        grid=(n_n, n_c),
        in_specs=[
            pl.BlockSpec((block_n,), lambda i, j: (i,)),
            pl.BlockSpec((block_n,), lambda i, j: (i,)),
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=sds((n_p, d), x.dtype, xp, yp, lp, gp),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        interpret=interpret,
    )(lp, gp, xp, yp)

    dy = pl.pallas_call(
        functools.partial(
            _bwd_dy_kernel, n_n_tiles=n_n, c_actual=c, block_c=block_c
        ),
        grid=(n_c, n_n),
        in_specs=[
            pl.BlockSpec((block_n,), lambda j, i: (i,)),
            pl.BlockSpec((block_n,), lambda j, i: (i,)),
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_c, d), lambda j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, d), lambda j, i: (j, 0)),
        out_shape=sds((c_p, d), y.dtype, xp, yp, lp, gp),
        scratch_shapes=[pltpu.VMEM((block_c, d), jnp.float32)],
        interpret=interpret,
    )(lp, gp, xp, yp)

    return dx[:n], dy[:c]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_lse(
    x, y, block_n: int = 256, block_c: int = 512, interpret: bool = False
):
    """Per-position full-catalog logsumexp, VMEM-streamed. → (N,) f32."""
    return _fwd(x, y, block_n=block_n, block_c=block_c, interpret=interpret)


def _vjp_fwd(x, y, block_n, block_c, interpret):
    lse = _fwd(x, y, block_n=block_n, block_c=block_c, interpret=interpret)
    return lse, (x, y, lse)


def _vjp_bwd(block_n, block_c, interpret, res, g):
    x, y, lse = res
    dx, dy = _bwd(
        x, y, lse, g, block_n=block_n, block_c=block_c, interpret=interpret
    )
    return psum_to(dx, x), psum_to(dy, y)


fused_lse.defvjp(_vjp_fwd, _vjp_bwd)


def fused_ce_loss(
    x,
    y,
    targets,
    block_n: int = 256,
    block_c: int = 512,
    interpret: bool = False,
):
    """Per-position full-CE loss ``lse(x·Yᵀ) − x·y_target``. → (N,)."""
    lse = fused_lse(x, y, block_n, block_c, interpret)
    pos = jnp.einsum(
        "nd,nd->n",
        x.astype(jnp.float32),
        jnp.take(y, targets, axis=0).astype(jnp.float32),
    )
    return (lse - pos).astype(x.dtype)
