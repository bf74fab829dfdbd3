"""Causal flash attention — Pallas TPU kernels, forward and backward.

The long-sequence training path of ``models/layers.attention``: causal
self-attention over a whole sequence (``q_offset = 0``, ``Lq = Lk``), no
window, softcap or key-validity mask. The jnp path it replaces computes
each query chunk's ``(chunk, H, Lk)`` float32 scores against every key,
the masked causal half included, and streams them through HBM for the
softmax's passes and again for the backward. Here each ``(block_q,
block_kv)`` score tile lives only in VMEM, and tiles wholly above the
diagonal are never computed.

Layout: ``q`` is ``(B·Hq, L, dqk)``, ``k`` is ``(B·Hkv, L, dqk)`` and ``v``
``(B·Hkv, L, dv)``; ``dqk`` and ``dv`` may differ (latent attention: 192
and 128). Blocks span the whole head width, so no width is padded. A
query head reads its key/value head through the ``BlockSpec`` index map
(``h // G``), so grouped-query attention never repeats ``k`` or ``v``.

Three ``pallas_call``s, each with the key (forward, dq) or query (dk/dv)
axis innermost and sequential, its running state in VMEM scratch:

  * ``mla_flash_fwd`` — grid ``(B·Hq, L/block_q, L/block_kv)``: online
    softmax (running max, sum and ``(block_q, dv)`` accumulator in
    float32); writes ``o`` and the per-row log-sum-exp;
  * ``mla_flash_dq`` — the same grid: recomputes ``P`` from ``q``, ``k``
    and the saved log-sum-exp, accumulates ``dq``;
  * ``mla_flash_dkv`` — grid ``(B·Hkv, L/block_kv, G·L/block_q)``, the
    query blocks of the ``G`` heads that share a key/value head inner:
    recomputes ``Pᵀ`` and accumulates ``dk`` and ``dv``.

A grid step whose tile lies wholly above the diagonal does nothing, and
its index map clamps to the nearest tile that is computed, so the
pipeline issues no DMA for it; only the tiles that straddle the diagonal
are masked. ``D = rowsum(dO ∘ O)`` is computed once, outside.

Precision (``mxu_dtype``): at ``bfloat16`` every tile is cast to
bfloat16 in VMEM just before it enters the MXU — one pass, what XLA's
``default`` precision does to the jnp path's float32 einsums on a TPU —
and ``P`` (``dS`` in the backward) is rounded to bfloat16 before its
matmul, as ``probs.astype(v.dtype)`` is there. At ``float32`` the tiles
are contracted at ``HIGHEST``. Accumulators and the softmax statistics
are float32 either way, and ``o``, ``dq``, ``dk``, ``dv`` leave the
kernels in their operands' dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import psum_to, sds

NEG_INF = -1e30
# The fastest pair of a sweep over {256, 512, 1024}² on a TPU v5e at
# L = 8,192, 16 heads, widths 192/128, that the guard's VMEM model
# admits (KERNELS.md).
BLOCK_Q = 512
BLOCK_KV = 1024

_NT = (((1,), (1,)), ((), ()))  # a @ bᵀ
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _mm(a, b, dims, mxu_dtype):
    """One MXU contraction with a float32 result: the operands rounded
    to bfloat16 (one pass), or float32 at HIGHEST."""
    wide = mxu_dtype == jnp.float32
    return jax.lax.dot_general(
        a.astype(mxu_dtype), b.astype(mxu_dtype), dims,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if wide else None,
    )


def _last_kv(i, block_q, block_kv):
    """The last key block query block ``i`` sees."""
    return ((i + 1) * block_q - 1) // block_kv


def _first_q(j, block_q, block_kv):
    """The first query block that sees key block ``j``."""
    return (j * block_kv) // block_q


def _straddles(i, j, block_q, block_kv):
    """Tile ``(i, j)`` holds a key after one of its queries."""
    return (j + 1) * block_kv - 1 > i * block_q


def _causal(shape, i, j, block_q, block_kv, *, transposed=False):
    """Keys at or before their query, for a ``(q, kv)`` tile (or a
    ``(kv, q)`` one when ``transposed``)."""
    q_ax, k_ax = (1, 0) if transposed else (0, 1)
    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_ax)
    k_pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, k_ax)
    return k_pos <= q_pos


def _on_tiles(i, j, block_q, block_kv, body):
    """Run ``body(masked)`` on tile ``(i, j)`` unless it lies wholly
    above the diagonal; only a straddling tile is masked."""
    run = j <= _last_kv(i, block_q, block_kv)
    edge = _straddles(i, j, block_q, block_kv)

    @pl.when(run & jnp.logical_not(edge))
    def _():
        body(False)

    @pl.when(run & edge)
    def _():
        body(True)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, block_q, block_kv, mxu_dtype):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        s = _mm(q_ref[...], k_ref[...], _NT, mxu_dtype) * scale
        if masked:
            s = jnp.where(_causal(s.shape, i, j, block_q, block_kv), s,
                          NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + _mm(p, v_ref[...], _NN,
                                                  mxu_dtype)
        m_scr[...] = m_new

    _on_tiles(i, j, block_q, block_kv, body)

    @pl.when(j == _last_kv(i, block_q, block_kv))
    def _finalize():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _q_map(bh, i, j):
    return bh, i, 0


def _fwd(q, k, v, *, scale, block_q, block_kv, mxu_dtype, interpret):
    bhq, seq, dqk = q.shape
    g, dv = bhq // k.shape[0], v.shape[-1]
    n_q, n_kv = seq // block_q, seq // block_kv

    def kv_map(bh, i, j):
        # row b·Hq + h reads row b·Hkv + h // G = (b·Hq + h) // G;
        # blocks past the last that query block i sees clamp to it
        return bh // g, jnp.minimum(j, _last_kv(i, block_q, block_kv)), 0

    col = pl.BlockSpec((None, block_q, 1), _q_map)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, mxu_dtype=mxu_dtype),
        grid=(bhq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, block_q, dqk), _q_map),
            pl.BlockSpec((None, block_kv, dqk), kv_map),
            pl.BlockSpec((None, block_kv, dv), kv_map),
        ],
        out_specs=[pl.BlockSpec((None, block_q, dv), _q_map), col],
        out_shape=[sds((bhq, seq, dv), v.dtype, q, k, v),
                   sds((bhq, seq, 1), jnp.float32, q, k, v)],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mla_flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward: dq
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, acc_scr,
               *, scale, block_q, block_kv, mxu_dtype):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        s = _mm(q_ref[...], k_ref[...], _NT, mxu_dtype) * scale
        p = jnp.exp(s - lse_ref[...])
        if masked:
            p = jnp.where(_causal(s.shape, i, j, block_q, block_kv), p, 0.0)
        dp = _mm(do_ref[...], v_ref[...], _NT, mxu_dtype)
        ds = p * (dp - d_ref[...])
        acc_scr[...] += _mm(ds, k_ref[...], _NN, mxu_dtype)

    _on_tiles(i, j, block_q, block_kv, body)

    @pl.when(j == _last_kv(i, block_q, block_kv))
    def _finalize():
        dq_ref[...] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dq(q, k, v, do, lse, delta, *, scale, block_q, block_kv, mxu_dtype,
        interpret):
    bhq, seq, dqk = q.shape
    g, dv = bhq // k.shape[0], v.shape[-1]

    def kv_map(bh, i, j):
        return bh // g, jnp.minimum(j, _last_kv(i, block_q, block_kv)), 0

    col = pl.BlockSpec((None, block_q, 1), _q_map)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, mxu_dtype=mxu_dtype),
        grid=(bhq, seq // block_q, seq // block_kv),
        in_specs=[
            pl.BlockSpec((None, block_q, dqk), _q_map),
            pl.BlockSpec((None, block_kv, dqk), kv_map),
            pl.BlockSpec((None, block_kv, dv), kv_map),
            pl.BlockSpec((None, block_q, dv), _q_map),
            col,
            col,
        ],
        out_specs=pl.BlockSpec((None, block_q, dqk), _q_map),
        out_shape=sds(q.shape, q.dtype, q, k, v, do),
        scratch_shapes=[pltpu.VMEM((block_q, dqk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mla_flash_dq",
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# Backward: dk, dv
# ---------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, block_q, block_kv, n_q, n_inner,
                mxu_dtype):
    j, t = pl.program_id(1), pl.program_id(2)
    i = t % n_q

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        # the tile transposed, (block_kv, block_q): every matmul is a @ b
        # or a @ bᵀ, and the statistics are rows
        st = _mm(k_ref[...], q_ref[...], _NT, mxu_dtype) * scale
        pt = jnp.exp(st - lse_ref[...])
        if masked:
            pt = jnp.where(_causal(st.shape, i, j, block_q, block_kv,
                                   transposed=True), pt, 0.0)
        dv_scr[...] += _mm(pt, do_ref[...], _NN, mxu_dtype)
        dpt = _mm(v_ref[...], do_ref[...], _NT, mxu_dtype)
        dst = pt * (dpt - d_ref[...])
        dk_scr[...] += _mm(dst, q_ref[...], _NN, mxu_dtype)

    _on_tiles(i, j, block_q, block_kv, body)

    @pl.when(t == n_inner - 1)
    def _finalize():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dkv(q, k, v, do, lse_row, delta_row, *, scale, block_q, block_kv,
         mxu_dtype, interpret):
    bhq, seq, dqk = q.shape
    bhk, dv = k.shape[0], v.shape[-1]
    g = bhq // bhk
    n_q, n_kv = seq // block_q, seq // block_kv

    def q_map(bh, j, t):
        # the t-th (head of the group, query block) pair: row b·Hkv + h
        # serves query rows (b·Hkv + h)·G + [0, G); blocks before the
        # first that sees key block j clamp to it
        row = bh * g + t // n_q
        return row, jnp.maximum(t % n_q, _first_q(j, block_q, block_kv)), 0

    def row_map(bh, j, t):
        row, i, _ = q_map(bh, j, t)
        return row, 0, i

    def kv_map(bh, j, t):
        return bh, j, 0

    row = pl.BlockSpec((None, 1, block_q), row_map)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, n_q=n_q, n_inner=g * n_q,
                          mxu_dtype=mxu_dtype),
        grid=(bhk, n_kv, g * n_q),
        in_specs=[
            pl.BlockSpec((None, block_q, dqk), q_map),
            pl.BlockSpec((None, block_kv, dqk), kv_map),
            pl.BlockSpec((None, block_kv, dv), kv_map),
            pl.BlockSpec((None, block_q, dv), q_map),
            row,
            row,
        ],
        out_specs=[pl.BlockSpec((None, block_kv, dqk), kv_map),
                   pl.BlockSpec((None, block_kv, dv), kv_map)],
        out_shape=[sds(k.shape, k.dtype, q, k, v, do),
                   sds(v.shape, v.dtype, q, k, v, do)],
        scratch_shapes=[pltpu.VMEM((block_kv, dqk), jnp.float32),
                        pltpu.VMEM((block_kv, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mla_flash_dkv",
    )(q, k, v, do, lse_row, delta_row)
    return dk, dv_


# ---------------------------------------------------------------------------
# Custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, block_q, block_kv, mxu_dtype, interpret):
    return _fwd(q, k, v, scale=scale, block_q=block_q, block_kv=block_kv,
                mxu_dtype=mxu_dtype, interpret=interpret)[0]


def _flash_fwd(q, k, v, scale, block_q, block_kv, mxu_dtype, interpret):
    o, lse = _fwd(q, k, v, scale=scale, block_q=block_q, block_kv=block_kv,
                  mxu_dtype=mxu_dtype, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, block_q, block_kv, mxu_dtype, interpret, res, do):
    q, k, v, o, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    kw = dict(scale=scale, block_q=block_q, block_kv=block_kv,
              mxu_dtype=mxu_dtype, interpret=interpret)
    dq = _dq(q, k, v, do, lse, delta, **kw)
    bhq, seq = lse.shape[:2]
    dk, dv = _dkv(q, k, v, do, lse.reshape(bhq, 1, seq),
                  delta.reshape(bhq, 1, seq), **kw)
    return psum_to(dq, q), psum_to(dk, k), psum_to(dv, v)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, scale, block_q=BLOCK_Q, block_kv=BLOCK_KV,
                    mxu_dtype=jnp.bfloat16, interpret=False):
    """Causal self-attention, ``q (B, L, Hq, dqk)``, ``k (B, L, Hkv,
    dqk)``, ``v (B, L, Hkv, dv)`` → ``(B, L, Hq, dv)``, differentiable.
    ``L`` is a multiple of both blocks."""
    b, seq, hq, dqk = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    assert seq % block_q == 0 and seq % block_kv == 0, (seq, block_q,
                                                        block_kv)

    def heads_first(x):
        return jnp.swapaxes(x, 1, 2).reshape(-1, seq, x.shape[-1])

    o = _flash(heads_first(q), heads_first(k), heads_first(v), scale,
               block_q, block_kv, mxu_dtype, interpret)
    return jnp.swapaxes(o.reshape(b, hq, seq, dv), 1, 2)
