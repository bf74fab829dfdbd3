"""Mixture-of-Experts FFN: token-choice top-k routing over every expert
of the layer, dropless computation of the experts this device holds.

A layer of ``n_experts`` is divided over the devices of an
expert-parallel group; this device holds ``experts_held`` of them, the
ids ``0 … held − 1`` (each device orders the router's columns and the
bias with its own experts first). Routing always runs over all
``n_experts``, so every device of the group makes the same choices.
The layer then computes:

  1. ``route``: the router logits at ``highest`` precision (the
     published implementations compute the gate in float32), the
     scores — ``softmax`` (granite: top-k of the softmax) or
     ``sigmoid`` (DeepSeek-V3 ``noaux_tc``: top-k of ``σ(x·W_r) + b``
     with ``b`` a non-trainable balancing bias, weights from the
     unbiased scores) — the gate weights ``routed_scaling · s_top /
     Σ s_top``, each expert's load over the whole batch, and the
     assignments to held experts sorted by expert;
  2. ``experts``: one grouped matmul per projection over the sorted
     assignments (``jax.lax.ragged_dot``). The buffer has room for
     ``tokens · min(top_k, experts_held)`` rows, every assignment that
     can reach a held expert, so none is ever dropped; rows past the
     last group are zeros and add nothing;
  3. ``shared``: the always-on shared experts, once per token.

What the absent experts add is computed on their own devices; summed
over the shares (and the shared experts counted once) the outputs are
the whole layer's.

The balance loss is ``E · Σ_i f_i · P_i`` per sequence, averaged over
sequences: ``f_i`` the share of the sequence's ``top_k`` assignments
that chose expert ``i`` and ``P_i`` its mean normalised score — Switch
Transformer's loss (2101.03961 §2.2) for the softmax router, and
DeepSeek-V3's sequence-wise balance loss (2412.19437 §2.1.2) for the
sigmoid one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

ROUTERS = ("softmax", "sigmoid")
TOKEN_BLOCK = 2048  # tokens whose held-expert buffers are live at once
# γ of a biased router's update after each step, b_i += γ·sign(mean load
# − load_i) (DeepSeek-V3, arXiv:2412.19437 §2.1.2)
BIAS_UPDATE_RATE = 1e-3


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int  # the router's outputs: every expert of the layer
    top_k: int
    d_ff: int  # per-expert hidden size
    n_shared_experts: int = 0  # always-on experts (DeepSeek/Kimi style)
    router: str = "softmax"
    routed_scaling: float = 1.0
    aux_loss_weight: float = 0.01
    # experts whose weights live on this device (None: all of them)
    experts_held: Optional[int] = None

    def __post_init__(self):
        if self.router not in ROUTERS:
            raise ValueError(f"router {self.router!r} not in {ROUTERS}")

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def has_bias(self) -> bool:
        """Whether the router selects with a balancing bias."""
        return self.router == "sigmoid"


def init_moe(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32):
    k_r, k_g, k_u, k_d, k_s = jax.random.split(key, 5)
    e, f = cfg.held, cfg.d_ff
    params = {
        "router": dense_init(k_r, (d_model, cfg.n_experts), dtype=jnp.float32),
        "w_gate": dense_init(k_g, (e, d_model, f), dtype=dtype),
        "w_up": dense_init(k_u, (e, d_model, f), dtype=dtype),
        "w_down": dense_init(k_d, (e, f, d_model), dtype=dtype),
    }
    if cfg.n_shared_experts:
        ks1, ks2, ks3 = jax.random.split(k_s, 3)
        fs = f * cfg.n_shared_experts
        params["shared"] = {
            "w_gate": dense_init(ks1, (d_model, fs), dtype=dtype),
            "w_up": dense_init(ks2, (d_model, fs), dtype=dtype),
            "w_down": dense_init(ks3, (fs, d_model), dtype=dtype),
        }
    return params


def route(router_w, x, cfg: MoEConfig, bias=None):
    """Top-k routing of ``x (T, D)`` over all experts.

    Returns ``(experts (T, k) int32, gates (T, k) f32, scores (T, E))``:
    the chosen experts, their weights, and the scores the balance loss
    reads."""
    logits = jnp.dot(x.astype(jnp.float32), router_w,
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.router == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        choice = scores
    else:
        scores = jax.nn.sigmoid(logits)
        choice = scores if bias is None else scores + bias
    _, experts = jax.lax.top_k(choice, cfg.top_k)
    top = jnp.take_along_axis(scores, experts, axis=-1)
    gates = cfg.routed_scaling * top / jnp.maximum(
        jnp.sum(top, axis=-1, keepdims=True), 1e-20)
    return experts, gates, scores


def balance_loss(experts, scores, n_experts: int, batch: int):
    """``E · Σ_i f_i · P_i`` per sequence, averaged over the ``batch``
    sequences of ``experts (T, k)`` and ``scores (T, E)``."""
    t, k = experts.shape
    chosen = jax.nn.one_hot(experts, n_experts, dtype=jnp.float32).sum(1)
    frac = chosen.reshape(batch, t // batch, n_experts).mean(1) / k
    p = scores / jnp.sum(scores, axis=-1, keepdims=True)
    mean_p = p.reshape(batch, t // batch, n_experts).mean(1)
    return n_experts * jnp.mean(jnp.sum(frac * mean_p, axis=-1))


def _held_part(params, xf, experts, gates, cfg: MoEConfig, activation):
    """The held experts' weighted outputs ``(T, D)`` for tokens ``xf``
    and their routing ``experts``/``gates`` ``(T, k)``: the held
    assignments sorted by expert into a buffer of ``T·min(k, held)``
    rows (every assignment that can reach a held expert, so none is
    dropped), one grouped matmul per projection, a scatter-add back.
    The TPU's ``ragged_dot`` leaves the rows past the last group
    undefined, in its transpose too: both ends of the buffer are masked
    to them."""
    t, d = xf.shape
    k, held = cfg.top_k, cfg.held
    with jax.named_scope("route"):
        # held assignments first, sorted by expert; the rest share the
        # sentinel group ``held`` and sort last
        flat = experts.reshape(-1)
        group = jnp.where(flat < held, flat, held)
        order = jnp.argsort(group, stable=True)[:t * min(k, held)]
        sizes = jnp.bincount(group, length=held + 1)[:held]
        tok = order // k
        live = (jnp.arange(order.shape[0]) < jnp.sum(sizes))[:, None]
        w = jnp.where(live[:, 0], gates.reshape(-1)[order], 0.0)
        xs = jnp.where(live, jnp.take(xf, tok, axis=0), 0.0)
    with jax.named_scope("experts"):
        h = activation(jax.lax.ragged_dot(xs, params["w_gate"], sizes))
        h = h * jax.lax.ragged_dot(xs, params["w_up"], sizes)
        ys = jnp.where(live, jax.lax.ragged_dot(h, params["w_down"], sizes),
                       0.0)
        return jnp.zeros((t, d), ys.dtype).at[tok].add(
            ys * w[:, None].astype(ys.dtype))


def apply_moe(params, x, cfg: MoEConfig, *, bias=None,
              activation=jax.nn.silu):
    """x: (B, L, D) → (y (B, L, D), balance loss, routing).

    ``y`` is this device's part of the layer: its held experts'
    weighted outputs plus the shared experts'. ``routing["load"]``
    counts each expert's (token, slot) assignments over the whole
    batch, ``(E,)`` int32; ``routing["experts"]`` holds each token's
    chosen experts, ``(B·L, top_k)``. The held experts run over blocks
    of ``TOKEN_BLOCK`` tokens, each recomputed in the backward pass, so
    that one block's buffers are live at a time."""
    b, l, d = x.shape
    t, k = b * l, cfg.top_k
    xf = x.reshape(t, d)
    with jax.named_scope("route"):
        experts, gates, scores = route(params["router"], xf, cfg, bias)
        load = jnp.bincount(experts.reshape(-1), length=cfg.n_experts)
        aux = cfg.aux_loss_weight * balance_loss(experts, scores,
                                                 cfg.n_experts, b)
    n = t // TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else 1
    part = jax.checkpoint(functools.partial(
        _held_part, params, cfg=cfg, activation=activation),
        prevent_cse=False)
    y = jax.lax.map(lambda a: part(*a), (
        xf.reshape(n, t // n, d), experts.reshape(n, t // n, k),
        gates.reshape(n, t // n, k))).reshape(t, d)
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            sp = params["shared"]
            g = activation(xf @ sp["w_gate"])
            y = y + (g * (xf @ sp["w_up"])) @ sp["w_down"]
    routing = {"load": load, "experts": experts}
    return y.reshape(b, l, d).astype(x.dtype), aux, routing
