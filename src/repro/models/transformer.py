"""Decoder-only transformer LM (llama/gemma/MoE-style), pure JAX.

Features required by the assigned architecture pool:
  * GQA (n_kv_heads < n_heads), RoPE, SwiGLU, RMSNorm (ε per config);
  * multi-head latent attention (DeepSeek-V2/V3 MLA, ``cfg.mla``):
    queries (optionally through a low-rank ``q_lora``), a shared
    compressed KV latent ``c_kv`` with one decoupled RoPE key ``k_pe``
    for all heads, keys and values reconstructed per head, run through
    the same ``layers.attention`` (the flash kernels at long training
    lengths); YaRN rope scaling (``rope_scaling``);
  * gemma-2: alternating local(sliding-window)/global attention layers,
    attention + final logit soft-capping, post-block norms, tied embeddings,
    embedding scaling by sqrt(d_model);
  * MoE FFN (moonlight, kimi-k2, granite) via repro.models.moe, after
    ``n_dense_layers`` leading dense layers (``first_k_dense_replace``);
    a biased (sigmoid) router's selection bias is the ``router_bias``
    leaf ``(n_moe_layers, E)``: state the train step updates from the
    experts' loads, never a weight (``trainable`` leaves it out);
  * layer stack as ``jax.lax.scan`` over stacked params (keeps HLO size
    O(1) in depth — essential for 62-layer AOT dry-runs) with optional
    ``jax.checkpoint`` remat per scanned step;
  * decode path with a dense KV cache (one-token step), window-aware,
    for GQA models without leading dense layers.

To keep the local/global pattern *static* (no double-computed attention),
the scan iterates over layer GROUPS of ``len(attn_pattern)`` layers; within
a group each layer's attention type is a Python constant.

The LM head is *not* applied here — ``forward`` returns final hidden states
and the (tied or separate) output embedding so the loss layer (full CE /
chunked CE / SCE) can decide how to touch the vocabulary. That choice is the
paper's entire subject.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import moe as moe_lib
from repro.models.layers import (
    YarnScaling,
    apply_rope,
    attention,
    dense_init,
    embed_init,
    rms_norm,
    swiglu,
    init_swiglu,
    yarn_mscale,
)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention sizes (DeepSeek-V2/V3 config keys)."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: Optional[int] = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float = 10000.0
    # attention pattern, tiled over layers: ("global",) or ("local","global")
    attn_pattern: Tuple[str, ...] = ("global",)
    window: Optional[int] = None
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    use_post_norm: bool = False  # gemma-2 style post-block norms
    tie_embeddings: bool = True
    scale_embeddings: bool = False  # gemma-style sqrt(d_model) scaling
    moe: Optional[moe_lib.MoEConfig] = None
    mla: Optional[MLAConfig] = None
    n_dense_layers: int = 0  # leading dense layers before the MoE ones
    norm_eps: float = 1e-6
    rope_scaling: Optional[YarnScaling] = None
    dtype: str = "float32"
    remat: bool = True
    q_chunk: int = 1024
    # Embedding rows are padded so the vocab-parallel table shards evenly
    # (standard practice — e.g. GPT-NeoX pads vocab to 128·TP). Padded
    # rows are phantom ids: never targets, maskable at serve time.
    vocab_pad_multiple: int = 16

    def __post_init__(self):
        assert self.n_scanned % len(self.attn_pattern) == 0, (
            "the scanned layers must be a multiple of the attention "
            "pattern length"
        )
        assert not self.n_dense_layers or (
            self.moe is not None and self.attn_pattern == ("global",)
        ), "leading dense layers precede MoE layers of one attention kind"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def vocab_padded(self) -> int:
        m = self.vocab_pad_multiple
        return -(-self.vocab // m) * m

    @property
    def n_scanned(self) -> int:
        """Layers of the main scan (the MoE layers of an MoE model)."""
        return self.n_layers - self.n_dense_layers

    @property
    def group_size(self) -> int:
        return len(self.attn_pattern)

    @property
    def n_groups(self) -> int:
        return self.n_scanned // self.group_size

    @property
    def softmax_scale(self) -> float:
        """1/sqrt of the query-key width, times YaRN's ``mscale²``."""
        dh = self.mla.qk_head_dim if self.mla else self.head_dim
        scale = dh**-0.5
        if self.rope_scaling is not None:
            y = self.rope_scaling
            scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
        return scale

    def _attn_params(self) -> int:
        d, h = self.d_model, self.n_heads
        m = self.mla
        if m is None:
            dh = self.head_dim
            return d * (h + 2 * self.n_kv_heads) * dh + h * dh * d
        if m.q_lora_rank:
            r = m.q_lora_rank
            q = d * r + r + r * h * m.qk_head_dim
        else:
            q = d * h * m.qk_head_dim
        kv = (d * (m.kv_lora_rank + m.qk_rope_head_dim) + m.kv_lora_rank
              + m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim))
        return q + kv + h * m.v_head_dim * d

    def param_count(self) -> int:
        """Weights this device holds (every expert when ``experts_held``
        is unset); the router bias is state, not counted."""
        d = self.d_model
        norms = (4 if self.use_post_norm else 2) * d
        dense = self._attn_params() + 3 * d * self.d_ff + norms
        if self.moe is not None:
            m = self.moe
            ffn = (m.held + m.n_shared_experts) * 3 * d * m.d_ff
            scanned = self._attn_params() + ffn + d * m.n_experts + norms
        else:
            scanned = dense
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return (self.n_dense_layers * dense + self.n_scanned * scanned
                + emb + d)

    def active_param_count(self) -> int:
        """Per-token active params (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        per_expert = 3 * self.d_model * m.d_ff
        return (self.param_count()
                - self.n_scanned * (m.held - m.top_k) * per_expert)


def _init_attn(key, cfg: TransformerConfig, n: int):
    """Attention weights of ``n`` stacked layers (GQA or MLA)."""
    dt = cfg.jnp_dtype
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)

    def stack(k, shape):
        return jax.vmap(lambda kk: dense_init(kk, shape, dtype=dt))(
            jax.random.split(k, n))

    m = cfg.mla
    if m is None:
        dh, hkv = cfg.head_dim, cfg.n_kv_heads
        return {
            "wq": stack(ks[0], (d, h * dh)),
            "wk": stack(ks[1], (d, hkv * dh)),
            "wv": stack(ks[2], (d, hkv * dh)),
            "wo": stack(ks[3], (h * dh, d)),
        }
    out = {
        "wkv_a": stack(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim)),
        "kv_norm": jnp.zeros((n, m.kv_lora_rank), dt),
        "wkv_b": stack(ks[2], (m.kv_lora_rank,
                               h * (m.qk_nope_head_dim + m.v_head_dim))),
        "wo": stack(ks[3], (h * m.v_head_dim, d)),
    }
    if m.q_lora_rank:
        ka, kb = jax.random.split(ks[0])
        out["wq_a"] = stack(ka, (d, m.q_lora_rank))
        out["q_norm"] = jnp.zeros((n, m.q_lora_rank), dt)
        out["wq_b"] = stack(kb, (m.q_lora_rank, h * m.qk_head_dim))
    else:
        out["wq"] = stack(ks[0], (d, h * m.qk_head_dim))
    return out


def _init_layers(key, cfg: TransformerConfig, n: int, moe: bool):
    dt, d = cfg.jnp_dtype, cfg.d_model
    k_attn, k_ffn = jax.random.split(key)
    layer = _init_attn(k_attn, cfg, n)
    layer["norm_attn"] = jnp.zeros((n, d), dt)
    layer["norm_mlp"] = jnp.zeros((n, d), dt)
    if cfg.use_post_norm:
        layer["norm_attn_post"] = jnp.zeros((n, d), dt)
        layer["norm_mlp_post"] = jnp.zeros((n, d), dt)
    if moe:
        layer["moe"] = jax.vmap(
            lambda kk: moe_lib.init_moe(kk, d, cfg.moe, dtype=dt)
        )(jax.random.split(k_ffn, n))
    else:
        layer["mlp"] = jax.vmap(
            lambda kk: init_swiglu(kk, d, cfg.d_ff, dtype=dt)
        )(jax.random.split(k_ffn, n))
    return layer


def init_params(key, cfg: TransformerConfig):
    dt, d = cfg.jnp_dtype, cfg.d_model
    keys = jax.random.split(key, 8)
    params = {
        "embed": embed_init(keys[5], (cfg.vocab_padded, d), dtype=dt),
        "norm_final": jnp.zeros((d,), dt),
        "layers": _init_layers(keys[4], cfg, cfg.n_scanned,
                               cfg.moe is not None),
    }
    if cfg.n_dense_layers:
        params["dense_layers"] = _init_layers(keys[3], cfg,
                                              cfg.n_dense_layers, False)
    if cfg.moe is not None and cfg.moe.has_bias:
        params["router_bias"] = jnp.zeros(
            (cfg.n_scanned, cfg.moe.n_experts), jnp.float32)
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(
            keys[6], (cfg.vocab_padded, d), dtype=dt
        )
    return params


def trainable(params):
    """The weights the optimizer updates: every leaf but the router's
    selection bias."""
    return {k: v for k, v in params.items() if k != "router_bias"}


def output_embedding(params, cfg: TransformerConfig):
    """Full (padded) output table — the training losses treat the padded
    rows as phantom negatives (never targets; standard vocab padding)."""
    return params["embed"] if cfg.tie_embeddings else params["unembed"]


def _group_params(cfg: TransformerConfig, layers):
    """Reshape stacked layer params (L, ...) → (n_groups, group, ...)."""
    g = cfg.group_size
    return jax.tree.map(
        lambda a: a.reshape((cfg.n_groups, g) + a.shape[1:]), layers
    )


def _gqa_qkv(cfg: TransformerConfig, h, lp, positions):
    b, l, _ = h.shape
    q = (h @ lp["wq"]).reshape(b, l, cfg.n_heads, cfg.head_dim)
    k = (h @ lp["wk"]).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ lp["wv"]).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mla_qkv(cfg: TransformerConfig, h, lp, positions):
    """Per-head q, k (no-PE part from the latent, the shared RoPE key
    appended) and v of latent attention."""
    m, eps = cfg.mla, cfg.norm_eps
    b, l, _ = h.shape
    nh, nope, rope = cfg.n_heads, m.qk_nope_head_dim, m.qk_rope_head_dim
    if m.q_lora_rank:
        q = rms_norm(h @ lp["wq_a"], lp["q_norm"], eps) @ lp["wq_b"]
    else:
        q = h @ lp["wq"]
    q = q.reshape(b, l, nh, nope + rope)
    kv_a = h @ lp["wkv_a"]
    c_kv = rms_norm(kv_a[..., :m.kv_lora_rank], lp["kv_norm"], eps)
    k_pe = kv_a[..., m.kv_lora_rank:].reshape(b, l, 1, rope)
    kv = (c_kv @ lp["wkv_b"]).reshape(b, l, nh, nope + m.v_head_dim)
    q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta,
                      cfg.rope_scaling)
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta, cfg.rope_scaling)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, l, nh, rope))], axis=-1)
    return q, k, kv[..., nope:]


def _attn_block(cfg: TransformerConfig, x, lp, positions, layer_type: str):
    b, l, _ = x.shape
    h = rms_norm(x, lp["norm_attn"], cfg.norm_eps)
    if cfg.mla is not None:
        with jax.named_scope("mla"):
            q, k, v = _mla_qkv(cfg, h, lp, positions)
            out = attention(q, k, v, causal=True, q_chunk=cfg.q_chunk,
                            scale=cfg.softmax_scale)
            out = out.reshape(b, l, -1) @ lp["wo"]
    else:
        q, k, v = _gqa_qkv(cfg, h, lp, positions)
        window = cfg.window if layer_type == "local" else None
        out = attention(
            q, k, v,
            causal=True,
            window=window,
            softcap=cfg.attn_softcap,
            q_chunk=cfg.q_chunk,
        )
        out = out.reshape(b, l, -1) @ lp["wo"]
    if cfg.use_post_norm:
        out = rms_norm(out, lp["norm_attn_post"], cfg.norm_eps)
    return out


def _mlp_block(cfg: TransformerConfig, x, lp, bias=None):
    """FFN of one layer: ``(out, balance loss, routing)``; ``routing``
    is ``apply_moe``'s (loads and chosen experts), ``None`` for a dense
    layer."""
    h = rms_norm(x, lp["norm_mlp"], cfg.norm_eps)
    if "moe" in lp:
        with jax.named_scope("moe"):
            out, aux, routing = moe_lib.apply_moe(lp["moe"], h, cfg.moe,
                                                  bias=bias)
    else:
        with jax.named_scope("dense_ffn"):
            out = swiglu(lp["mlp"], h)
        aux, routing = jnp.zeros((), jnp.float32), None
    if cfg.use_post_norm:
        out = rms_norm(out, lp["norm_mlp_post"], cfg.norm_eps)
    return out, aux, routing


def forward(params, cfg: TransformerConfig, tokens, positions=None):
    """tokens: (B, L) int32 → (hidden (B, L, D), aux).

    ``aux["balance"]`` is the summed balance loss of the MoE layers (0
    without them); an MoE model's ``aux["load"]`` holds each MoE
    layer's (E,) expert loads, ``(n_moe_layers, E)``, and
    ``aux["experts"]`` each token's chosen experts,
    ``(n_moe_layers, B·L, top_k)``."""
    b, l = tokens.shape
    if positions is None:
        positions = jnp.arange(l)[None, :]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)

    def layer(x, lp, layer_type, bias):
        x = x + _attn_block(cfg, x, lp, positions, layer_type)
        mlp_out, aux, routing = _mlp_block(cfg, x, lp, bias)
        return x + mlp_out, aux, routing

    def dense_body(x, lp):
        return layer(x, lp, "global", None)[0], None

    def body(x, inp):
        group_lp, group_bias = inp
        aux_total = jnp.zeros((), jnp.float32)
        routing = []
        for gi, layer_type in enumerate(cfg.attn_pattern):
            lp = jax.tree.map(lambda a: a[gi], group_lp)
            bias = None if group_bias is None else group_bias[gi]
            x, aux, r = layer(x, lp, layer_type, bias)
            aux_total = aux_total + aux
            routing.append(r)
        if routing[0] is None:
            return x, (aux_total, None)
        return x, (aux_total, jax.tree.map(lambda *a: jnp.stack(a),
                                           *routing))

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
        # XLA inlines a scan of one layer, where the recomputation would
        # be merged with the forward and every activation kept
        dense_body = jax.checkpoint(
            dense_body, prevent_cse=cfg.n_dense_layers == 1)
    if cfg.n_dense_layers:
        x, _ = jax.lax.scan(dense_body, x, params["dense_layers"])
    bias = params.get("router_bias")
    if bias is not None:
        bias = bias.reshape(cfg.n_groups, cfg.group_size, -1)
    grouped = _group_params(cfg, params["layers"])
    x, (auxes, routing) = jax.lax.scan(body, x, (grouped, bias))
    x = rms_norm(x, params["norm_final"], cfg.norm_eps)
    aux = {"balance": jnp.sum(auxes)}
    if routing is not None:
        aux.update(jax.tree.map(
            lambda a: a.reshape((cfg.n_scanned,) + a.shape[2:]), routing))
    return x, aux


def logits_from_hidden(params, cfg: TransformerConfig, hidden):
    """Full logits (use only for small vocab / decode single position).
    Phantom (padding) vocab rows are masked to -inf for sampling safety."""
    logits = hidden @ output_embedding(params, cfg).T
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    if cfg.vocab_padded != cfg.vocab:
        ids = jnp.arange(cfg.vocab_padded)
        logits = jnp.where(ids < cfg.vocab, logits, -1e30)
    return logits


def prefill(params, cfg: TransformerConfig, tokens, *,
            cache_len: Optional[int] = None, act_spec=None):
    """Process a full prompt and return ``(hidden, cache)``.

    The cache follows the ``init_cache`` layout: global layers keep all
    ``cache_len`` (default: prompt length) positions; local layers keep a
    rolling ``window``-sized cache holding the last ``window`` positions
    at slots ``p mod window`` — exactly what ``decode_step`` expects when
    continuing from ``pos = prompt_len``.

    ``act_spec`` (a PartitionSpec) pins the residual stream's sharding at
    every layer boundary — pass ``P(dp, "model", None)`` for sequence
    parallelism so per-layer K/V are born in the seq-sharded cache layout.
    """
    _check_kv_cache(cfg)
    b, s = tokens.shape
    cache_len = cache_len or s
    positions = jnp.arange(s)[None, :]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)

    def constrain(t):
        if act_spec is None:
            return t
        return jax.lax.with_sharding_constraint(t, act_spec)

    x = constrain(x)
    grouped = _group_params(cfg, params["layers"])

    def _to_cache(k_or_v, layer_type: str):
        """(B, S, Hkv, dh) → cache slice for one layer."""
        if layer_type == "local" and cfg.window is not None:
            w = min(cfg.window, cache_len)
            if s >= w:
                # last w positions, placed at slots p mod w
                rel = (jnp.arange(w) - s) % w
                out = jax.lax.dynamic_slice_in_dim(k_or_v, s - w, w, axis=1)
                out = jnp.take(out, rel, axis=1)
            else:
                out = jnp.pad(k_or_v, ((0, 0), (0, w - s), (0, 0), (0, 0)))
            return out
        if s >= cache_len:
            return k_or_v[:, :cache_len]
        return jnp.pad(
            k_or_v, ((0, 0), (0, cache_len - s), (0, 0), (0, 0))
        )

    def body(x, group_lp):
        kvs = {}
        for gi, layer_type in enumerate(cfg.attn_pattern):
            lp = jax.tree.map(lambda a: a[gi], group_lp)
            h = rms_norm(x, lp["norm_attn"], cfg.norm_eps)
            q, k, v = _gqa_qkv(cfg, h, lp, positions)
            window = cfg.window if layer_type == "local" else None
            out = attention(
                q, k, v,
                causal=True,
                window=window,
                softcap=cfg.attn_softcap,
                q_chunk=cfg.q_chunk,
            )
            out = out.reshape(b, s, -1) @ lp["wo"]
            if cfg.use_post_norm:
                out = rms_norm(out, lp["norm_attn_post"], cfg.norm_eps)
            x = x + out
            mlp_out, _, _ = _mlp_block(cfg, x, lp)
            x = constrain(x + mlp_out)
            kvs[f"k{gi}"] = _to_cache(k, layer_type)
            kvs[f"v{gi}"] = _to_cache(v, layer_type)
        return x, kvs

    x, cache = jax.lax.scan(body, x, grouped)
    x = rms_norm(x, params["norm_final"], cfg.norm_eps)
    return x, cache


# ---------------------------------------------------------------------------
# Decode path (single-token step over a dense KV cache)
# ---------------------------------------------------------------------------
def _check_kv_cache(cfg: TransformerConfig) -> None:
    if cfg.mla is not None or cfg.n_dense_layers or (
            cfg.moe is not None and cfg.moe.has_bias):
        raise NotImplementedError(
            "prefill/decode keep a per-head GQA cache of one layer kind; "
            "latent attention, leading dense layers and biased routers "
            "have no cache path")


def init_cache(cfg: TransformerConfig, batch: int, max_len: int, dtype=None):
    """Dense KV cache. Local (sliding-window) layers allocate only a
    ``window``-sized rolling cache — for gemma2 @ 500k context this is a
    128× cache reduction on half the layers."""
    _check_kv_cache(cfg)
    dtype = dtype or cfg.jnp_dtype
    g = cfg.group_size
    caches = {}
    for gi, layer_type in enumerate(cfg.attn_pattern):
        length = (
            min(cfg.window, max_len)
            if (layer_type == "local" and cfg.window is not None)
            else max_len
        )
        shape = (cfg.n_groups, batch, length, cfg.n_kv_heads, cfg.head_dim)
        caches[f"k{gi}"] = jnp.zeros(shape, dtype)
        caches[f"v{gi}"] = jnp.zeros(shape, dtype)
    return caches


def decode_step(params, cfg: TransformerConfig, cache, tokens, pos):
    """One decode step. tokens: (B, 1); pos: scalar current position.

    Returns (logits (B, 1, V), new_cache). Global layers mask cache entries
    at positions > pos; local layers use a rolling (mod-window) cache.
    """
    _check_kv_cache(cfg)
    b = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    positions = jnp.full((b, 1), pos)
    grouped = _group_params(cfg, params["layers"])

    def body(x, inp):
        group_lp = inp[0]
        new_caches = {}
        for gi, layer_type in enumerate(cfg.attn_pattern):
            lp = jax.tree.map(lambda a: a[gi], group_lp)
            k_cache = inp[1][f"k{gi}"]
            v_cache = inp[1][f"v{gi}"]
            cache_len = k_cache.shape[1]
            is_local = layer_type == "local" and cfg.window is not None
            slot = jnp.mod(pos, cache_len) if is_local else pos

            h = rms_norm(x, lp["norm_attn"], cfg.norm_eps)
            q, k_new, v_new = _gqa_qkv(cfg, h, lp, positions)
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k_new.astype(k_cache.dtype), (0, slot, 0, 0)
            )
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v_new.astype(v_cache.dtype), (0, slot, 0, 0)
            )
            kv_idx = jnp.arange(cache_len)
            if is_local:
                # rolling cache: entry at index i holds some position ≡ i
                # (mod window) that is ≤ pos and > pos - window by
                # construction — every filled slot is valid once pos ≥
                # window; before that, mask unfilled slots.
                valid = (kv_idx[None, :] <= pos) | jnp.full(
                    (1, cache_len), pos >= cache_len
                )
            else:
                valid = kv_idx[None, :] <= pos
            valid = jnp.broadcast_to(valid, (b, cache_len))
            attn_out = attention(
                q, k_cache, v_cache,
                causal=False,  # masking handled via kv_valid
                softcap=cfg.attn_softcap,
                kv_valid=valid,
            )
            attn_out = attn_out.reshape(b, 1, -1) @ lp["wo"]
            if cfg.use_post_norm:
                attn_out = rms_norm(attn_out, lp["norm_attn_post"],
                                    cfg.norm_eps)
            x = x + attn_out
            mlp_out, _, _ = _mlp_block(cfg, x, lp)
            x = x + mlp_out
            new_caches[f"k{gi}"] = k_cache
            new_caches[f"v{gi}"] = v_cache
        return x, new_caches

    x, new_cache = jax.lax.scan(body, x, (grouped, cache))
    x = rms_norm(x, params["norm_final"], cfg.norm_eps)
    logits = logits_from_hidden(params, cfg, x)
    return logits, new_cache
