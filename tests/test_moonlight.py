"""moonlight-16b-a3b against its plain reference (``bench/reference/
moonlight.py``) on seeded random weights at the smoke size, on the CPU:
the forward pass and loss, every leaf's gradient, the parameters and
the router's selection bias after three steps; the expert-share
identity; no assignment dropped under a skewed router; and the softmax
router (granite) against the layer's former dropless output."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import moonlight as ref  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import SmokeShape  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.optim.optimizers import adamw  # noqa: E402

ARCH = get_arch("moonlight-16b-a3b")
CFG = ARCH.make_smoke_config()
BATCH, SEQ = 2, 16
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}


def _sizes(cfg=CFG, **over):
    """The reference's sizes (``config.json`` keys) of a program config."""
    m, e = cfg.mla, cfg.moe
    sizes = {
        "vocab_size": cfg.vocab, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_dense_layers,
        "intermediate_size": cfg.d_ff, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim,
        "v_head_dim": m.v_head_dim, "n_routed_experts": e.n_experts,
        "experts_held": e.held, "num_experts_per_tok": e.top_k,
        "moe_intermediate_size": e.d_ff,
        "n_shared_experts": e.n_shared_experts,
        "routed_scaling_factor": e.routed_scaling,
        "aux_loss_weight": e.aux_loss_weight,
        "bias_update_rate": moe_lib.BIAS_UPDATE_RATE,
    }
    sizes.update(over)
    return sizes


def _batch(seed=0, batch=BATCH, seq=SEQ):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(1, CFG.vocab, (batch, seq)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    valid = np.ones((batch, seq), bool)
    valid[:, -1] = False
    return {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(targets),
            "valid": jnp.asarray(valid)}


def _make_step(batch, seq):
    mesh = make_host_mesh(max_data=batch)
    shape = SmokeShape("train_smoke", "train",
                       {"global_batch": batch, "seq_len": seq})
    fn, (opt_init, _), sce_cfg = steps_lib.make_lm_train_step(
        ARCH, CFG, mesh, shape, sce_mode="exact")
    sce = {"n_b": sce_cfg.n_buckets, "b_x": sce_cfg.bucket_size_x,
           "b_y": sce_cfg.bucket_size_y}
    return jax.jit(fn), opt_init, sce


@pytest.fixture(scope="module")
def step():
    return _make_step(BATCH, SEQ)


def _close(a, b, rtol=2e-4, atol=2e-6):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol)


def _close_leaf(a, b, share=1e-4):
    """Elementwise, to ``share`` of the leaf's largest magnitude: float32
    sums in another order differ in the last bits of small elements."""
    b = np.asarray(b)
    _close(a, b, rtol=0, atol=share * float(np.max(np.abs(b))) + 1e-12)


def test_forward_matches_reference():
    params = ref.init_params(jax.random.PRNGKey(0), _sizes())
    batch = _batch()
    hidden, aux = tf.forward(params, CFG, batch["tokens"])
    want, bal, load, experts = ref.forward(params, _sizes(),
                                           batch["tokens"])
    _close(hidden, want)
    _close(aux["balance"], CFG.moe.aux_loss_weight * bal)
    np.testing.assert_array_equal(np.asarray(aux["load"]),
                                  np.asarray(load))
    np.testing.assert_array_equal(
        np.sort(np.asarray(aux["experts"]), -1),
        np.sort(np.asarray(experts).reshape(aux["experts"].shape), -1))


def test_train_steps_match_reference(step):
    """Loss, the experts step 1 chose, every leaf's first gradient
    (AdamW's first moment over 1 − β1), and the parameters and
    selection bias after three steps."""
    _check_three_steps(*step, [_batch(i) for i in range(3)])


def test_flash_train_steps_match_reference():
    """The same three steps over one sequence of 2,048 tokens, two of
    the flash kernels' key blocks, a length whose attention runs them
    (interpret mode). Loss, experts and first gradients to the same
    limits; the parameters after three steps to 5e-4 of each leaf's
    largest: at such lengths AdamW's normalised steps carry float32
    summation order further (at 1,024 tokens the chunked jnp path
    itself read 1.3e-4, the kernel 2.2e-4)."""
    fn, opt_init, sce = _make_step(1, 2048)
    batches = [_batch(i, batch=1, seq=2048) for i in range(3)]
    params = ref.init_params(jax.random.PRNGKey(1), _sizes())
    jaxpr = str(jax.make_jaxpr(fn)(params, opt_init(params), batches[0],
                                   jax.random.PRNGKey(2)))
    for name in ("mla_flash_fwd", "mla_flash_dq", "mla_flash_dkv"):
        assert name in jaxpr, name
    _check_three_steps(fn, opt_init, sce, batches, param_share=5e-4)


def _check_three_steps(fn, opt_init, sce, batches, param_share=1e-4):
    sizes = _sizes()
    key = jax.random.PRNGKey(1)
    params = ref.init_params(key, sizes)
    opt = opt_init(params)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)

    bias = params["router_bias"]
    train_p = ref.trainable(params)
    r_init, r_update = adamw(OPT["lr"], b1=OPT["b1"], b2=OPT["b2"],
                             eps=OPT["eps"],
                             weight_decay=OPT["weight_decay"])
    r_opt = r_init(train_p)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b, batch, k: ref.train_loss(p, b, sizes, sce, batch, k),
        has_aux=True))
    for i, (batch, k) in enumerate(zip(batches, keys)):
        if i == 0:
            want_experts = ref.forward(params, sizes, batch["tokens"])[3]
        params, opt, metrics = fn(params, opt, batch, k)
        (loss, load), grads = grad_fn(train_p, bias, batch, k)
        _close(metrics["loss"], loss, rtol=1e-5)
        assert not bool(metrics["skipped"])
        if i == 0:
            # the step's own choices, (tokens, layers, k)
            got = np.swapaxes(np.asarray(metrics["moe_experts"]), 0, 1)
            np.testing.assert_array_equal(
                np.sort(got, -1),
                np.sort(np.asarray(want_experts).reshape(got.shape), -1))
            got = jax.tree.map(lambda m: m / (1 - OPT["b1"]),
                               opt.inner["m"])
            jax.tree.map(_close_leaf, got, grads)
        train_p, r_opt = r_update(grads, r_opt, train_p)
        bias = bias + sizes["bias_update_rate"] * jnp.sign(
            jnp.mean(load, -1, keepdims=True) - load)
    jax.tree.map(lambda a, b: _close_leaf(a, b, param_share),
                 tf.trainable(params), train_p)
    _close(params["router_bias"], bias, rtol=0, atol=1e-7)
    assert float(jnp.max(jnp.abs(bias - ref.init_params(
        key, sizes)["router_bias"]))) > 0  # the bias moved


def test_step_experts_concatenated_over_microbatches():
    """The step's ``moe_experts`` in two microbatches are the one-batch
    step's: each microbatch's tokens in the batch's order."""
    mesh = make_host_mesh(max_data=BATCH)
    shape = SmokeShape("train_smoke", "train",
                       {"global_batch": BATCH, "seq_len": SEQ})
    params = ref.init_params(jax.random.PRNGKey(1), _sizes())
    batch, key = _batch(), jax.random.PRNGKey(2)
    got = []
    for n_micro in (1, 2):
        fn, (opt_init, _), _ = steps_lib.make_lm_train_step(
            ARCH, CFG, mesh, shape, sce_mode="exact",
            n_micro_override=n_micro)
        metrics = jax.jit(fn)(params, opt_init(params), batch, key)[2]
        got.append(np.asarray(metrics["moe_experts"]))
    assert got[0].shape == (BATCH * SEQ, CFG.n_layers - CFG.n_dense_layers,
                            CFG.moe.top_k)
    np.testing.assert_array_equal(got[1], got[0])


def _layer_sizes(held, n_experts=8, top_k=3):
    return _sizes(n_routed_experts=n_experts, experts_held=held,
                  num_experts_per_tok=top_k)


def _moe_cfg(held, n_experts=8, top_k=3, router="sigmoid"):
    return moe_lib.MoEConfig(
        n_experts=n_experts, top_k=top_k, d_ff=CFG.moe.d_ff,
        n_shared_experts=2, router=router, routed_scaling=2.446,
        experts_held=held)


def test_expert_shares_add_up_to_the_layer():
    """8 experts in 4 shares of 2: the shares' partial outputs, with the
    shared experts counted once, add up to the uncut reference layer."""
    d = CFG.d_model
    full_cfg = _moe_cfg(8)
    params = moe_lib.init_moe(jax.random.PRNGKey(3), d, full_cfg)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    x = jax.random.normal(jax.random.PRNGKey(5), (BATCH, SEQ, d))

    share_cfg = _moe_cfg(2)
    # what every share computes alike
    shared = ref._swiglu(x.reshape(-1, d), params["shared"],
                         "highest").reshape(x.shape)
    total = shared
    for s in range(4):
        # share s holds experts 2s, 2s+1: its numbering puts them first
        part = {k: (jnp.roll(v, -2 * s, axis=0)[:2] if k.startswith("w_")
                    else v) for k, v in params.items()}
        part["router"] = jnp.roll(params["router"], -2 * s, axis=1)
        y, _, routing = moe_lib.apply_moe(part, x, share_cfg,
                                          bias=jnp.roll(bias, -2 * s))
        total = total + (y - shared)
    want = jax.vmap(lambda xs: ref._experts(
        xs, params, bias, _layer_sizes(8), "highest", frozenset())[0])(x)
    _close(total, want, rtol=1e-4, atol=1e-5)
    assert int(routing["load"].sum()) == BATCH * SEQ * 3


def test_no_assignment_dropped_under_skewed_router():
    """A bias that sends every token to experts 0–2, all held: each of
    the T·k assignments is computed (no capacity), as the dense
    per-token reference computes them."""
    d = CFG.d_model
    cfg = _moe_cfg(4)
    params = moe_lib.init_moe(jax.random.PRNGKey(6), d, cfg)
    bias = jnp.zeros((8,)).at[:3].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, d))
    y, _, routing = moe_lib.apply_moe(params, x, cfg, bias=bias)
    t = BATCH * SEQ
    np.testing.assert_array_equal(np.asarray(routing["load"]),
                                  [t, t, t, 0, 0, 0, 0, 0])
    want = jax.vmap(lambda xs: ref._experts(
        xs, params, bias, _layer_sizes(4), "highest", frozenset())[0])(x)
    _close(y, want, rtol=1e-4, atol=1e-5)


def _former_softmax_layer(params, x, cfg):
    """The softmax top-k layer as it computed before it became dropless,
    wherever its capacity dropped nothing: top-k of the softmax,
    renormalised over the chosen experts, each chosen expert's SwiGLU
    weighted by it."""
    probs = jax.nn.softmax(jnp.einsum("bld,de->ble", x, params["router"],
                                      precision="highest"), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    w = jnp.sum(jax.nn.one_hot(top_e, cfg.n_experts) * top_p[..., None], 2)
    gate = jax.nn.silu(jnp.einsum("bld,edf->blef", x, params["w_gate"]))
    up = jnp.einsum("bld,edf->blef", x, params["w_up"])
    y_e = jnp.einsum("blef,efd->bled", gate * up, params["w_down"])
    return jnp.einsum("bled,ble->bld", y_e, w)


def test_granite_softmax_router_matches_former_output():
    cfg = get_arch("granite-moe-3b-a800m").make_smoke_config().moe
    d = 64
    params = moe_lib.init_moe(jax.random.PRNGKey(8), d, cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (BATCH, SEQ, d))
    y, aux, routing = moe_lib.apply_moe(params, x, cfg)
    _close(y, _former_softmax_layer(params, x, cfg), rtol=1e-4, atol=1e-6)
    assert np.isfinite(float(aux))
    assert int(routing["load"].sum()) == BATCH * SEQ * cfg.top_k
