"""Shared neural-net layers (pure JAX, functional params-as-pytrees).

Conventions:
  * every layer is a pair of functions ``init_*(key, ...) -> params`` and a
    pure ``apply`` that takes the params dict first;
  * weights are stored in named dicts so sharding-spec trees can mirror the
    structure 1:1 (see repro/dist/sharding.py);
  * causal self-attention over whole long sequences runs the flash
    kernels (``kernels/flash_attention.py``); every other attention is
    computed in query chunks with an explicit mask per chunk — the
    (B, Lq, H, Lk) score tensor is never materialized beyond one chunk,
    which is what lets 32k-token prefill compile inside a 16 GB HBM budget.

Weight-layout conventions (DESIGN.md §2; built ONLY by
``repro.dist.sharding`` — models never construct PartitionSpecs):
  * matmul weights are stored ``(d_in, d_out)`` and applied as
    ``x @ w``, so "column-parallel" = shard dim -1 over ``model``
    (wq/wk/wv, gate/up projections) and "row-parallel" = shard dim -2
    (wo, down projections) — the Megatron pairing that needs one
    collective per block;
  * embedding/catalog tables are ``(rows, d)`` with rows padded to a
    shard-even multiple; rows shard over ``model`` (vocab-parallel),
    padded rows are phantoms (never targets, masked at serve);
  * stacked per-layer params carry a leading ``(L, ...)`` scan dim that
    is never sharded; norms/biases replicate unless their matmul's
    output dim is sharded (then they follow it);
  * KV caches are ``(n_groups, B, len, H_kv, dh)``: batch over the data
    axes, KV heads over ``model`` (see ``transformer_cache_specs`` for
    the GQA/long-context fallbacks).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as flash
from repro.kernels import ops

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun-style)."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    if scale is None:
        scale = 1.0 / max(fan_in, 1) ** 0.5
    return (
        jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * scale
    ).astype(dtype)


def embed_init(key, shape, scale: float = 0.02, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(x, gamma, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))
    return out.astype(dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    out = out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A ``rope_scaling`` of ``type`` yarn, as a model's config states it."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


def rope_frequencies(head_dim: int, theta: float = 10000.0,
                     yarn: Optional[YarnScaling] = None):
    """Rotary frequencies ``(head_dim/2,)``. ``yarn`` blends the
    interpolated ``f/factor`` into the low frequencies by YaRN's linear
    ramp (DeepSeek-V3's ``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask``)."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta**exponents)  # (head_dim/2,)
    if yarn is None:
        return freqs

    def correction_dim(rotations):
        orig = yarn.original_max_position_embeddings
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1·mscale·ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float = 10000.0,
               yarn: Optional[YarnScaling] = None):
    """x: (..., L, H, dh); positions: broadcastable to (..., L)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., L, dh/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., L, 1, dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window / softcap / bidirectional)
# ---------------------------------------------------------------------------
def _softcap(scores, cap: Optional[float]):
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _attn_one_chunk(
    q_c,  # (B, c, Hkv, G, dh)
    k,  # (B, Lk, Hkv, dh)
    v,  # (B, Lk, Hkv, dh)
    q_pos_c,  # (c,) global positions of the chunk queries
    kv_pos,  # (Lk,) global positions of keys
    *,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    kv_valid: Optional[jax.Array],  # (B, Lk) bool, e.g. decode cache fill
    scale: float,
):
    scores = jnp.einsum(
        "bchgd,blhd->bchgl", q_c, k, preferred_element_type=jnp.float32
    ) * scale
    scores = _softcap(scores, softcap)
    mask = jnp.ones((q_c.shape[1], k.shape[1]), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos_c[:, None]
    if window is not None:
        mask &= q_pos_c[:, None] - kv_pos[None, :] < window
    scores = jnp.where(mask[None, :, None, None, :], scores, NEG_INF)
    if kv_valid is not None:
        scores = jnp.where(
            kv_valid[:, None, None, None, :], scores, NEG_INF
        )
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bchgl,blhd->bchgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(v.dtype)


def _attn_one_chunk_flat(
    q_c,  # (B, c, Hq, dh) — single flat head dim (TP-shardable)
    k,  # (B, Lk, Hq, dh) — kv already expanded to query heads
    v,  # (B, Lk, Hq, dh)
    q_pos_c,
    kv_pos,
    *,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    kv_valid: Optional[jax.Array],
    scale: float,
):
    """Long-sequence path with ONE head dim. The grouped (Hkv, G) split
    cannot be sharded over a 16-way model axis when Hq = 8·7 etc., which
    makes GSPMD replicate q (an involuntary-remat all-gather of the whole
    activation); a flat 56-head dim shards (with padding) just fine."""
    scores = jnp.einsum(
        "bchd,blhd->bchl", q_c, k, preferred_element_type=jnp.float32
    ) * scale
    scores = _softcap(scores, softcap)
    mask = jnp.ones((q_c.shape[1], k.shape[1]), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos_c[:, None]
    if window is not None:
        mask &= q_pos_c[:, None] - kv_pos[None, :] < window
    scores = jnp.where(mask[None, :, None, :], scores, NEG_INF)
    if kv_valid is not None:
        scores = jnp.where(kv_valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bchl,blhd->bchd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(v.dtype)


def attention(
    q,  # (B, Lq, Hq, dh)
    k,  # (B, Lk, Hkv, dh)
    v,  # (B, Lk, Hkv, dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset=0,  # global position of q[0] (decode: cache length so far)
    kv_valid: Optional[jax.Array] = None,
    q_chunk: int = 1024,
    scale: Optional[float] = None,
):
    """Grouped-query attention. ``v`` may be narrower than ``q``/``k``
    (latent attention: 128 against 192); ``scale`` defaults to
    ``1/sqrt(dh)`` of ``q``.

    Causal self-attention over a whole sequence — no ``window``,
    ``softcap`` or ``kv_valid``, ``q_offset`` 0, ``Lq == Lk``, at least
    two flash blocks long and a multiple of them — runs the flash
    kernels (``ops.causal_attention``): no score leaves VMEM and tiles
    above the diagonal are skipped. Everything else (short sequences,
    bidirectional, sliding windows, softcaps, prefill and decode against
    a cache) runs :func:`chunked_attention`.
    """
    lq = q.shape[1]
    if scale is None:
        scale = q.shape[-1]**-0.5
    block = max(flash.BLOCK_Q, flash.BLOCK_KV)
    if (causal and window is None and softcap is None and kv_valid is None
            and isinstance(q_offset, int) and q_offset == 0
            and lq == k.shape[1] and lq >= 2 * block
            and lq % flash.BLOCK_Q == 0 and lq % flash.BLOCK_KV == 0):
        return ops.causal_attention(q, k, v, scale=scale).astype(q.dtype)
    return chunked_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, kv_valid=kv_valid, q_chunk=q_chunk, scale=scale)


def chunked_attention(q, k, v, *, causal, window, softcap, q_offset,
                      kv_valid, q_chunk, scale):
    """:func:`attention` in jnp, computed in query chunks: peak score
    memory is ``B × q_chunk × Hq × Lk`` instead of ``B × Lq × Hq × Lk``,
    and the backward under ``jax.checkpoint`` recomputes chunks."""
    b, lq, hq, dh = q.shape
    dv = v.shape[-1]
    hkv = k.shape[2]
    g = hq // hkv
    kv_pos = jnp.arange(k.shape[1])
    q_pos = q_offset + jnp.arange(lq)

    if lq <= q_chunk:
        # short-q / decode path: grouped heads, k/v stay at Hkv (the
        # (Hkv, G) reshape of a tiny q is harmless)
        qg = q.reshape(b, lq, hkv, g, dh)
        out = _attn_one_chunk(
            qg, k, v, q_pos, kv_pos,
            causal=causal, window=window, softcap=softcap, kv_valid=kv_valid,
            scale=scale,
        )
        return out.reshape(b, lq, hq, dv).astype(q.dtype)

    assert lq % q_chunk == 0, (lq, q_chunk)
    n_chunks = lq // q_chunk

    # long-q path: flat head dim (TP-shardable — see _attn_one_chunk_flat)
    # with llama-style repeat_kv via a head-map gather
    if hkv != hq:
        head_map = jnp.arange(hq) // g
        k = jnp.take(k, head_map, axis=2)
        v = jnp.take(v, head_map, axis=2)

    # checkpoint per chunk: the scan's reverse pass would otherwise stack
    # every chunk's (B, c, Hq, Lk) probs — n_chunks× the flash-attention
    # working set. Recomputed per chunk instead.
    chunk_fn = jax.checkpoint(
        functools.partial(
            _attn_one_chunk_flat,
            causal=causal, window=window, softcap=softcap, kv_valid=kv_valid,
            scale=scale,
        ),
        prevent_cse=False,
    )

    # chunks cut with dynamic_slice on the SEQUENCE axis only, leaving the
    # head dim's sharding untouched
    def step(_, i):
        q_c = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        q_pos_c = jax.lax.dynamic_slice_in_dim(q_pos, i * q_chunk, q_chunk)
        out = chunk_fn(q_c, k, v, q_pos_c, kv_pos)
        return None, out

    _, outs = jax.lax.scan(
        step, None, jnp.arange(n_chunks)
    )  # (n_chunks, B, c, Hq, dv)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, lq, hq, dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def init_swiglu(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype=dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype=dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype=dtype),
    }


def swiglu(params, x, activation=jax.nn.silu):
    gate = activation(x @ params["w_gate"])
    return (gate * (x @ params["w_up"])) @ params["w_down"]


def init_mlp(key, sizes, dtype=jnp.float32):
    """Plain MLP: sizes = (d_in, h1, ..., d_out). ReLU between layers."""
    keys = jax.random.split(key, len(sizes) - 1)
    return {
        f"w{i}": dense_init(keys[i], (sizes[i], sizes[i + 1]), dtype=dtype)
        for i in range(len(sizes) - 1)
    } | {
        f"b{i}": jnp.zeros((sizes[i + 1],), dtype) for i in range(len(sizes) - 1)
    }


def mlp_apply(params, x, activation=jax.nn.relu, final_activation=None):
    n = len([k for k in params if k.startswith("w")])
    for i in range(n):
        x = x @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1:
            x = activation(x)
        elif final_activation is not None:
            x = final_activation(x)
    return x
