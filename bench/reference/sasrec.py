"""Plain float32 reference of SASRec with the SCE loss and AdamW.

Written from the published descriptions, in straightforward
``jax.numpy``, and independent of the program under test:

* SASRec (Kang & McAuley, ICDM 2018) as the SCE paper trains it
  (arXiv:2409.18721 §3.3, §4.1.3): item embeddings scaled by
  ``sqrt(d)`` plus learned positional embeddings; pre-norm blocks of
  causal multi-head self-attention and a GELU (tanh) feed-forward, each
  with a residual; LayerNorm (eps 1e-6) at the end. Dropout is off, as
  in the program's train step. Padding positions are ordinary inputs
  (no key mask); the loss leaves them out.
* SCE, Algorithm 1 of the paper with Mix (§3.2): bucket centres
  ``B = Ω·X / sqrt(N)`` from a standard normal ``Ω`` over the valid
  positions; per bucket the top ``b_x`` positions and top ``b_y``
  catalog rows by inner product (ties to the lower index); in-bucket
  cross-entropy against the explicit positive logit with the positive
  masked out of the negatives; per position the largest bucket loss;
  the mean over the valid positions that some bucket covers.
* AdamW (Loshchilov & Hutter) with bias correction.

The parameter tree has the program's layout (``item_emb``, ``pos_emb``,
``ln_f_*``, and the layers stacked on a leading axis) so that the
weights the benchmark makes from the seed feed both. Every contraction
goes through ``precision.einsum`` at the given ``mode``: ``"highest"``
is the reference; a lower mode is the control.

How the program draws its randomness is part of its contract with the
caller, and mirrored here: the step key splits into three and the
second, folded with the data-shard index (0 on one chip), draws Ω.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from reference.precision import einsum

LN_EPS = 1e-6


def n_rows(sizes: Dict) -> int:
    """Embedding rows: the catalog padded to ``row_pad_multiple``."""
    m = sizes["row_pad_multiple"]
    return -(-sizes["n_items"] // m) * m


def init_params(key, sizes: Dict):
    """Seeded weights: embeddings N(0, 0.02²); projections truncated
    normal at ±2σ with σ = 1/sqrt(fan_in); biases 0; norm gains 1."""
    d, ff, L = sizes["d_model"], sizes["d_ff"], sizes["n_layers"]
    k = jax.random.split(key, 6)

    def dense(kk, shape):
        return jax.random.truncated_normal(
            kk, -2.0, 2.0, (L,) + shape, jnp.float32) / math.sqrt(shape[0])

    return {
        "item_emb": 0.02 * jax.random.normal(k[0], (n_rows(sizes), d)),
        "pos_emb": 0.02 * jax.random.normal(k[1], (sizes["max_len"], d)),
        "ln_f_g": jnp.ones((d,)),
        "ln_f_b": jnp.zeros((d,)),
        "layers": {
            "wqkv": dense(k[2], (d, 3 * d)),
            "wo": dense(k[3], (d, d)),
            "w1": dense(k[4], (d, ff)),
            "w2": dense(k[5], (ff, d)),
            "b1": jnp.zeros((L, ff)),
            "b2": jnp.zeros((L, d)),
            "ln1_g": jnp.ones((L, d)),
            "ln1_b": jnp.zeros((L, d)),
            "ln2_g": jnp.ones((L, d)),
            "ln2_b": jnp.zeros((L, d)),
        },
    }


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def forward(params, sizes: Dict, tokens, mode: str = "highest"):
    """Hidden states ``(B, L, d)`` of right-aligned ``(B, L)`` ids."""
    d, h = sizes["d_model"], sizes["n_heads"]
    bsz, seq = tokens.shape
    dh = d // h
    x = params["item_emb"][tokens] * math.sqrt(d) + params["pos_emb"][:seq]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    lay = params["layers"]
    for i in range(sizes["n_layers"]):
        y = _layer_norm(x, lay["ln1_g"][i], lay["ln1_b"][i])
        qkv = einsum("bld,de->ble", y, lay["wqkv"][i], mode)
        q, k, v = (a.reshape(bsz, seq, h, dh) for a in jnp.split(qkv, 3, -1))
        s = einsum("bqhe,bkhe->bhqk", q, k, mode) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = einsum("bhqk,bkhe->bqhe", p, v, mode).reshape(bsz, seq, d)
        x = x + einsum("bld,de->ble", o, lay["wo"][i], mode)
        y = _layer_norm(x, lay["ln2_g"][i], lay["ln2_b"][i])
        f = jax.nn.gelu(einsum("bld,df->blf", y, lay["w1"][i], mode)
                        + lay["b1"][i], approximate=True)
        x = x + einsum("blf,fd->bld", f, lay["w2"][i], mode) + lay["b2"][i]
    return _layer_norm(x, params["ln_f_g"], params["ln_f_b"])


def sce_loss(x, y, targets, valid, key, n_b: int, b_x: int, b_y: int,
             mode: str = "highest"):
    """SCE over ``x`` ``(N, d)`` outputs and catalog ``y`` ``(C, d)``."""
    n, _ = x.shape
    xs, ys = jax.lax.stop_gradient(x), jax.lax.stop_gradient(y)
    omega = jax.random.normal(key, (n_b, n), jnp.float32) * valid
    centres = einsum("bn,nd->bd", omega, xs, mode) / math.sqrt(n)
    xp = jnp.where(valid, einsum("bd,nd->bn", centres, xs, mode), -jnp.inf)
    idx_x = jax.lax.top_k(xp, b_x)[1]
    idx_y = jax.lax.top_k(einsum("bd,cd->bc", centres, ys, mode), b_y)[1]
    x_b, t_b = x[idx_x], targets[idx_x]
    pos = jnp.sum(x_b * y[t_b], axis=-1)
    neg = einsum("bxd,byd->bxy", x_b, y[idx_y], mode)
    neg = jnp.where(idx_y[:, None, :] == t_b[:, :, None], -jnp.inf, neg)
    lse = jax.nn.logsumexp(jnp.concatenate([pos[..., None], neg], -1), -1)
    per_pos = jax.ops.segment_max((lse - pos).ravel(), idx_x.ravel(),
                                  num_segments=n)
    covered = jnp.zeros((n,), bool).at[idx_x.ravel()].set(True) & valid
    return (jnp.sum(jnp.where(covered, per_pos, 0.0))
            / jnp.maximum(jnp.sum(covered), 1))


def _step_inputs(params, sizes: Dict, batch, step_key, mode: str):
    key = jax.random.fold_in(jax.random.split(step_key, 3)[1], 0)
    h = forward(params, sizes, batch["tokens"], mode)
    return h.reshape(-1, h.shape[-1]), key


def train_loss(params, sizes: Dict, sce: Dict, batch, step_key,
               mode: str = "highest"):
    """The loss of one train step on ``batch`` (tokens, targets, valid)."""
    x, key = _step_inputs(params, sizes, batch, step_key, mode)
    # every padded row is a catalog row: a candidate, never a target
    return sce_loss(x, params["item_emb"], batch["targets"].ravel(),
                    batch["valid"].ravel(), key, sce["n_b"], sce["b_x"],
                    sce["b_y"], mode)


def selection_margins(params, sizes: Dict, sce: Dict, batch, step_key,
                      mode: str = "highest"):
    """How near SCE's selection of one step is to a tie: over the
    buckets, the smallest gap between the last selected and the first
    left-out score, relative to the bucket's largest score magnitude,
    for the positions (top ``b_x``) and the catalog (top ``b_y``). A
    gap near round-off means the program's selection can differ from
    the reference's by one row without a fault on either side."""
    x, key = _step_inputs(params, sizes, batch, step_key, mode)
    valid = batch["valid"].ravel()
    n = x.shape[0]
    omega = jax.random.normal(key, (sce["n_b"], n), jnp.float32) * valid
    centres = einsum("bn,nd->bd", omega, x, mode) / math.sqrt(n)
    xp = jnp.where(valid, einsum("bd,nd->bn", centres, x, mode), -jnp.inf)
    yp = einsum("bd,cd->bc", centres, params["item_emb"], mode)

    def margin(s, k):
        top = jax.lax.top_k(s, k + 1)[0]
        scale = jnp.max(jnp.abs(jnp.where(jnp.isfinite(s), s, 0.0)), axis=1)
        return jnp.min((top[:, k - 1] - top[:, k]) / scale)

    return margin(xp, sce["b_x"]), margin(yp, sce["b_y"])


def adamw(params, grads, m, v, step: int, opt: Dict):
    """One AdamW update at 1-based ``step``; returns (params, m, v)."""
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, a, b: p - opt["lr"] * ((a / c1) / (jnp.sqrt(b / c2)
                                                     + opt["eps"])
                                         + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of each leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(jnp.square(a))))
            for p, a in flat}


def train_readings(key, sizes: Dict, sce: Dict, opt: Dict, batches,
                   step_keys, mode: str = "highest") -> Dict:
    """Three AdamW steps from the seeded weights: each step's loss, the
    norm of each leaf of the first gradient and of the parameters'
    change after the steps, and each step's ``selection_margins``."""
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        train_loss, sizes=sizes, sce=sce, mode=mode)))
    p0 = jax.jit(functools.partial(init_params, sizes=sizes))(key)
    params = p0
    m = jax.tree.map(jnp.zeros_like, p0)
    v = jax.tree.map(jnp.zeros_like, p0)
    margins = jax.jit(functools.partial(selection_margins, sizes=sizes,
                                        sce=sce, mode=mode))
    losses, g1, near = [], None, []
    for i, (batch, sk) in enumerate(zip(batches, step_keys), start=1):
        loss, grads = grad_fn(params, batch=batch, step_key=sk)
        losses.append(float(loss))
        near.append([float(a) for a in margins(params, batch=batch,
                                               step_key=sk)])
        if g1 is None:
            g1 = leaf_norms(grads)
        params, m, v = adamw(params, grads, m, v, i, opt)
    delta = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return {"losses": losses, "grad": g1, "delta": delta, "margins": near}


def scores(states, catalog, mode: str = "highest"):
    """``(U, d)`` states against ``(C, d)`` rows: ``(U, C)`` scores."""
    return einsum("ud,cd->uc", states, catalog, mode)
