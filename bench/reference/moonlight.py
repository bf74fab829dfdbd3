"""Plain float32 reference of Moonlight-16B-A3B's layers with the SCE
loss and AdamW, over one device's share of the experts.

Written from the published description (DeepSeek-V3, arXiv:2412.19437
§2.1; the model's ``config.json``, ``model_type`` deepseek_v3) in
straightforward ``jax.numpy``, independent of the program under test:

* embedding lookup; layer 0 dense, the rest MoE; pre-norm RMSNorm
  (``eps`` from the config) before attention and FFN, each with a
  residual; a final RMSNorm. Norm gains are stored as offsets from 1
  (``x/rms · (1 + g)``), the program's layout of the same weight.
* MLA: ``q = x·W_q`` split per head into ``q_nope`` and ``q_pe``;
  ``[c_kv; k_pe] = x·W_kv_a``; ``c_kv`` RMS-normed; ``[k_nope; v] =
  c_kv·W_kv_b`` per head; RoPE (θ from the config, rotating the two
  halves of each vector: the published checkpoint's interleaved pairs
  permuted, as its own loading code does) on ``q_pe`` and the one
  ``k_pe`` all heads share; scores ``(q_nope·k_nope + q_pe·k_pe)/√192``
  under a causal softmax; ``softmax·v``, then ``W_o``.
* Expert layer: ``s = σ(x·W_r)`` over all ``n_routed_experts``; the top
  ``num_experts_per_tok`` of ``s + b`` (``b`` the router's selection
  bias, not a weight); weights ``g = routed_scaling_factor · s_top /
  Σ s_top``; ``y = Σ g_i·FFN_i(x) + FFN_shared(x)`` with SwiGLU FFNs,
  the shared one ``n_shared_experts`` times as wide. Of the routed
  experts only the ``experts_held`` this device holds (ids ``0 …
  held−1``) are computed, each densely over every token with its
  weight 0 where it was not chosen.
* The sequence-wise balance loss ``α·E·Σ_i f_i·P_i`` (``f_i`` the share
  of a sequence's assignments that chose expert ``i``, ``P_i`` its mean
  score normalised over the experts), averaged over sequences.
* After each step the selection bias moves by ``γ·sign(mean load −
  load_i)`` per layer, from that step's assignment counts.
* SCE and AdamW: ``reference/sasrec.py``'s, by import.

Attention runs over blocks of queries and the experts over blocks of
tokens, each block and each layer recomputed in the backward pass, and
AdamW's moments wait in host memory while a gradient is taken, so that
the reference fits one chip at the cell's size. Every contraction
but the router's goes through ``precision.einsum`` at ``mode``; the
router's logits are float32 at ``highest`` always, as the published
implementation computes the gate.

``faults`` plants a known fault for the correctness check's controls:
``no_shared`` (shared experts left out), ``no_bias`` (selection bias
ignored), ``capacity`` (per sequence and expert, assignments past
``tokens·k/E`` dropped, token order), ``zero_k_pe`` (the RoPE key zeroed).

How the program draws its randomness is mirrored: Ω is drawn from the
step key folded with the data-shard index (0 on one chip).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, FrozenSet

import jax
import jax.numpy as jnp
import numpy as np

from reference import sasrec
from reference.precision import einsum

FAULTS = ("no_shared", "no_bias", "capacity", "zero_k_pe")
Q_BLOCK = 512
TOKEN_BLOCK = 2048
BIAS_SCALE = 0.01  # the seeded selection bias's standard deviation


def init_params(key, sizes: Dict):
    """Seeded weights in the program's layout: embeddings N(0, 0.02²);
    projections truncated normal at ±2σ with σ = 1/sqrt(fan_in); norm
    offsets 0; the selection bias N(0, BIAS_SCALE²), a bias partway
    through training (a fresh run's zeros would leave the biased
    selection out of the first step)."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, r = sizes["v_head_dim"], sizes["kv_lora_rank"]
    e, held = sizes["n_routed_experts"], sizes["experts_held"]
    f = sizes["moe_intermediate_size"]
    fs = f * sizes["n_shared_experts"]
    n_dense = sizes["first_k_dense_replace"]
    n_moe = sizes["num_hidden_layers"] - n_dense
    ks = iter(jax.random.split(key, 32))

    def dense(n, *shape):
        return (jax.random.truncated_normal(next(ks), -2.0, 2.0,
                                            (n,) + shape, jnp.float32)
                / math.sqrt(shape[-2]))

    def attn(n):
        return {
            "wq": dense(n, d, h * (nope + rope)),
            "wkv_a": dense(n, d, r + rope),
            "kv_norm": jnp.zeros((n, r)),
            "wkv_b": dense(n, r, h * (nope + dv)),
            "wo": dense(n, h * dv, d),
            "norm_attn": jnp.zeros((n, d)),
            "norm_mlp": jnp.zeros((n, d)),
        }

    ff = sizes["intermediate_size"]
    dense_layers = attn(n_dense)
    dense_layers["mlp"] = {"w_gate": dense(n_dense, d, ff),
                           "w_up": dense(n_dense, d, ff),
                           "w_down": dense(n_dense, ff, d)}
    layers = attn(n_moe)
    layers["moe"] = {
        "router": dense(n_moe, d, e),
        "w_gate": dense(n_moe, held, d, f),
        "w_up": dense(n_moe, held, d, f),
        "w_down": dense(n_moe, held, f, d),
        "shared": {"w_gate": dense(n_moe, d, fs),
                   "w_up": dense(n_moe, d, fs),
                   "w_down": dense(n_moe, fs, d)},
    }
    rows = sizes["vocab_size"]
    return {
        "embed": 0.02 * jax.random.normal(next(ks), (rows, d)),
        "unembed": 0.02 * jax.random.normal(next(ks), (rows, d)),
        "norm_final": jnp.zeros((d,)),
        "dense_layers": dense_layers,
        "layers": layers,
        "router_bias": BIAS_SCALE * jax.random.normal(next(ks), (n_moe, e)),
    }


def trainable(params):
    return {k: v for k, v in params.items() if k != "router_bias"}


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * (1.0 + g)


def _rope(x, theta):
    """``x (T, …, dh)`` at positions ``0 … T−1``."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dh // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(x, lp, sizes, mode, faults):
    """MLA over one sequence ``x (T, d)``."""
    t = x.shape[0]
    h, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, r = sizes["v_head_dim"], sizes["kv_lora_rank"]
    q = einsum("td,de->te", x, lp["wq"], mode).reshape(t, h, nope + rope)
    kv_a = einsum("td,de->te", x, lp["wkv_a"], mode)
    c_kv = _rms(kv_a[:, :r], lp["kv_norm"], eps)
    k_pe = _rope(kv_a[:, r:], sizes["rope_theta"])
    if "zero_k_pe" in faults:
        k_pe = jnp.zeros_like(k_pe)
    kv = einsum("tr,re->te", c_kv, lp["wkv_b"], mode).reshape(t, h,
                                                             nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], sizes["rope_theta"])
    scale = 1.0 / math.sqrt(nope + rope)
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(i):
        rows = jax.lax.dynamic_slice_in_dim
        qn, qp = rows(q_nope, i, qb), rows(q_pe, i, qb)
        s = (einsum("qhe,khe->hqk", qn, k_nope, mode)
             + einsum("qhe,ke->hqk", qp, k_pe, mode)) * scale
        causal = jnp.arange(t)[None, :] <= (i + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return einsum("hqk,khe->qhe", p, v, mode)

    starts = jnp.arange(0, t, qb)
    o = jax.lax.map(block, starts).reshape(t, h * dv)
    return einsum("te,ed->td", o, lp["wo"], mode)


def _swiglu(x, w, mode):
    g = einsum("td,df->tf", x, w["w_gate"], mode)
    u = einsum("td,df->tf", x, w["w_up"], mode)
    return einsum("tf,fd->td", jax.nn.silu(g) * u, w["w_down"], mode)


def route(x, router, bias, sizes, faults=frozenset()):
    """Over all experts: ``(experts (T, k), gates (T, k), scores (T, E))``."""
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x, router,
                                  precision=jax.lax.Precision.HIGHEST))
    choice = s if "no_bias" in faults else s + bias
    experts = jax.lax.top_k(choice, k)[1]
    top = jnp.take_along_axis(s, experts, -1)
    gates = sizes["routed_scaling_factor"] * top / jnp.sum(top, -1,
                                                           keepdims=True)
    return experts, gates, s


def _experts(x, lp, bias, sizes, mode, faults):
    """One sequence ``x (T, d)``: (held experts' part + shared, balance
    loss, (E,) load, (T, k) chosen experts)."""
    t = x.shape[0]
    e, k, held = (sizes["n_routed_experts"], sizes["num_experts_per_tok"],
                  sizes["experts_held"])
    experts, gates, s = route(x, lp["router"], bias, sizes, faults)
    chosen = jax.nn.one_hot(experts, e).sum(1)  # (T, E)
    if "capacity" in faults:
        cap = max(1, int(t * k / e))
        rank = jnp.cumsum(chosen, 0) - chosen  # earlier tokens first
        kept = (rank < cap) & (chosen > 0)
        slot_kept = jnp.take_along_axis(kept, experts, -1)
        gates = jnp.where(slot_kept, gates, 0.0)
    # (T, held) weight of each held expert for each token
    weight = jnp.sum(jax.nn.one_hot(experts, e)[..., :held]
                     * gates[..., None], axis=1)

    @jax.checkpoint
    def block(inp):
        xb, wb = inp
        g = einsum("td,edf->tef", xb, lp["w_gate"], mode)
        u = einsum("td,edf->tef", xb, lp["w_up"], mode)
        y = einsum("tef,efd->ted", jax.nn.silu(g) * u, lp["w_down"], mode)
        return jnp.sum(y * wb[..., None], axis=1)

    n = t // TOKEN_BLOCK if t % TOKEN_BLOCK == 0 and t > TOKEN_BLOCK else 1
    out = jax.lax.map(block, (x.reshape(n, t // n, -1),
                              weight.reshape(n, t // n, held)))
    out = out.reshape(t, -1)
    if "no_shared" not in faults:
        out = out + _swiglu(x, lp["shared"], mode)
    p = s / jnp.sum(s, -1, keepdims=True)
    bal = e * jnp.sum(jnp.mean(chosen, 0) / k * jnp.mean(p, 0))
    return out, bal, jnp.sum(chosen, 0), experts


def forward(params, sizes: Dict, tokens, mode: str = "highest",
            faults: FrozenSet[str] = frozenset()):
    """Hidden states ``(B, T, d)``, the balance loss, each MoE layer's
    (E,) load over the batch and its ``(B, T, k)`` chosen experts."""
    eps = sizes["rms_norm_eps"]

    @jax.checkpoint
    def dense_layer(x, lp):
        x = x + _attention(_rms(x, lp["norm_attn"], eps), lp, sizes, mode,
                           faults)
        return x + _swiglu(_rms(x, lp["norm_mlp"], eps), lp["mlp"], mode)

    def one(seq):
        x = params["embed"][seq]
        for i in range(sizes["first_k_dense_replace"]):
            x = dense_layer(x, jax.tree.map(lambda a: a[i],
                                            params["dense_layers"]))
        bal, loads, routes = 0.0, [], []

        @jax.checkpoint
        def moe_layer(x, lp, bias):
            x = x + _attention(_rms(x, lp["norm_attn"], eps), lp, sizes,
                               mode, faults)
            y, b, load, experts = _experts(_rms(x, lp["norm_mlp"], eps),
                                           lp["moe"], bias, sizes, mode,
                                           faults)
            return x + y, b, load, experts

        for i in range(params["router_bias"].shape[0]):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, b, load, experts = moe_layer(x, lp,
                                            params["router_bias"][i])
            bal, loads, routes = bal + b, loads + [load], routes + [experts]
        return (_rms(x, params["norm_final"], eps), bal, jnp.stack(loads),
                jnp.stack(routes))

    hs, bals, loads, routes = jax.vmap(one)(tokens)
    return (hs, jnp.mean(bals), jnp.sum(loads, 0),
            jnp.moveaxis(routes, 0, 1))


def train_loss(train_p, bias, sizes: Dict, sce: Dict, batch, step_key,
               mode: str = "highest", faults: FrozenSet[str] = frozenset()):
    """One step's loss (SCE plus the weighted balance loss) and the
    MoE layers' loads."""
    params = dict(train_p, router_bias=bias)
    h, bal, load, _ = forward(params, sizes, batch["tokens"], mode, faults)
    key = jax.random.fold_in(step_key, 0)
    loss = sasrec.sce_loss(
        h.reshape(-1, h.shape[-1]), train_p["unembed"],
        batch["targets"].ravel(), batch["valid"].ravel(), key,
        sce["n_b"], sce["b_x"], sce["b_y"], mode)
    return loss + sizes["aux_loss_weight"] * bal, load


def routes(params, sizes: Dict, tokens, mode: str = "highest",
           faults: FrozenSet[str] = frozenset()):
    """The ``(n_moe_layers, B, T, k)`` experts each token chose."""
    return forward(params, sizes, tokens, mode, faults)[3]


def _adamw_step(train_p, grads, m, v, step, opt):
    return sasrec.adamw(train_p, grads, m, v, step, opt)


@functools.lru_cache(maxsize=None)
def _programs(sizes_items, sce_items, opt_items, mode, faults):
    """The jitted init, first routes, gradient, update and change norms,
    built once per configuration, precision and fault set in a process
    (every seed of ``bench/readings.py`` reuses them)."""
    sizes, sce, opt = dict(sizes_items), dict(sce_items), dict(opt_items)
    init = jax.jit(functools.partial(init_params, sizes=sizes))
    first = jax.jit(functools.partial(routes, sizes=sizes, mode=mode,
                                      faults=faults))
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        train_loss, sizes=sizes, sce=sce, mode=mode, faults=faults),
        has_aux=True))
    update = jax.jit(functools.partial(_adamw_step, opt=opt),
                     donate_argnums=(0, 2, 3))
    delta = jax.jit(lambda p, k: _delta_norms(p, init(k)))
    return init, first, grad_fn, update, delta


def _items(d: Dict):
    return tuple(sorted((k, v) for k, v in d.items()
                        if not isinstance(v, (dict, list))))


def train_readings(key, sizes: Dict, sce: Dict, opt: Dict, batches,
                   step_keys, mode: str = "highest",
                   faults: FrozenSet[str] = frozenset()) -> Dict:
    """Three steps from the seeded weights: each step's loss, the norm of
    each leaf of the first gradient, of each leaf's change after the
    steps (the selection bias's too), and step 1's chosen experts."""
    init, first_fn, grad_fn, update, delta_fn = _programs(
        _items(sizes), _items(sce), _items(opt), mode, frozenset(faults))
    gamma = sizes["bias_update_rate"]
    p0 = init(key)
    first = first_fn(p0, tokens=batches[0]["tokens"])
    bias = p0["router_bias"]
    train_p = trainable(p0)
    del p0
    # AdamW's moments wait in host memory while the gradient is taken
    m = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), train_p)
    v = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), train_p)
    losses, g1 = [], None
    for i, (batch, sk) in enumerate(zip(batches, step_keys), start=1):
        (loss, load), grads = grad_fn(train_p, bias, batch=batch,
                                      step_key=sk)
        losses.append(float(loss))
        if g1 is None:
            g1 = sasrec.leaf_norms(grads)
        train_p, m, v = update(train_p, grads, jax.device_put(m),
                               jax.device_put(v), jnp.int32(i))
        del grads
        m, v = jax.device_get((m, v))
        bias = bias + gamma * jnp.sign(jnp.mean(load, -1, keepdims=True)
                                       - load)
    del m, v
    delta = delta_fn(dict(train_p, router_bias=bias), key)
    return {"losses": losses, "grad": g1,
            "delta": {k: float(x) for k, x in delta.items()},
            "routes": first}


def _delta_norms(p, p0):
    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(jnp.subtract, p, p0))[0]
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(a)))
            for path, a in flat}
