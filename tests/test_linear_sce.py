"""Fused linear-SCE training step (kernels/linear_sce.py + the softcap
kernel unification): the hidden states never meet an ``(N, V)`` logit
matrix, forward or backward.

Covers:
  * the linear CE kernel vs the dense oracle — loss, dX, dW, softcap on
    and off, at deliberately non-multiple shapes;
  * duplicate targets (same tile AND across dW RMW revisits);
  * a jaxpr structural assertion: no intermediate of size ``N·V``
    anywhere in the forward-plus-backward jaxpr (dense ``ce`` is the
    positive control that the walker actually sees such tensors);
  * the exactness limit: kernel-path SCE with ``b_x ≥ N, b_y ≥ V``
    equals naive ``ce`` / ``ce_fused`` on loss and both grads;
  * softcapped ``use_kernel=True`` SCE configs actually TAKE the kernel
    path now (regression for the removed ``logit_softcap is None``
    gate) and match the jnp path on loss and grads.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import losses as L
from repro.core import sce as sce_lib
from repro.core.sce import SCEConfig, sce_loss
from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _problem(seed=0, n=48, c=300, d=12, scale=1.0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (n, d)) * scale
    y = jax.random.normal(ks[1], (c, d)) * scale
    t = jax.random.randint(ks[2], (n,), 0, c)
    return x, y, t


def _dense_ce_mean(x, y, t, logit_softcap=None):
    logits = jnp.dot(x, y.T, preferred_element_type=jnp.float32)
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    lse = jax.nn.logsumexp(logits, axis=-1)
    pos = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - pos)


def _kernel_ce_mean(x, y, t, logit_softcap=None):
    per_pos = ops.linear_ce_loss(
        x, y, t, logit_softcap=logit_softcap,
        block_n=16, block_c=64, interpret=True,
    )
    return jnp.mean(per_pos)


def _check_loss_and_grads(x, y, t, logit_softcap):
    l0, (dx0, dy0) = jax.value_and_grad(
        _dense_ce_mean, argnums=(0, 1))(x, y, t, logit_softcap)
    l1, (dx1, dy1) = jax.value_and_grad(
        _kernel_ce_mean, argnums=(0, 1))(x, y, t, logit_softcap)
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    np.testing.assert_allclose(dx1, dx0, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(dy1, dy0, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("n,c,d", [(48, 300, 12), (17, 130, 8)])
def test_linear_ce_matches_dense(n, c, d):
    """Kernel loss/dX/dW == dense oracle at non-multiple-of-block shapes."""
    x, y, t = _problem(n=n, c=c, d=d)
    _check_loss_and_grads(x, y, t, None)


def test_linear_ce_softcap_matches_dense():
    """Softcap applied INSIDE the tile: capped CE and its exact grads
    (the tanh derivative flows through both dX and dW)."""
    x, y, t = _problem(scale=4.0)
    _check_loss_and_grads(x, y, t, 10.0)


def test_linear_ce_ref_matches_dense():
    """The chunked jnp oracle (shard_map fallback path) matches dense."""
    x, y, t = _problem(scale=4.0)
    for cap in (None, 10.0):
        per_pos = ref.linear_ce_loss_ref(x, y, t, logit_softcap=cap, chunk=64)
        dense = _dense_ce_mean(x, y, t, cap)
        np.testing.assert_allclose(jnp.mean(per_pos), dense, rtol=1e-5)


def test_linear_ce_duplicate_targets_dw_rmw():
    """Many rows sharing one target — the dW accumulator revisits the
    same ``(block_c, d)`` tile across every row-block RMW pass — and a
    target column hit from rows in different row blocks."""
    x, y, _ = _problem(n=40, c=150, d=8)
    # all rows in row-block 0 and 2 share target 7; block 1 spreads out
    t = jnp.array([7] * 16 + list(range(16)) + [7] * 8, dtype=jnp.int32)
    _check_loss_and_grads(x, y, t, None)
    _check_loss_and_grads(x * 4, y * 4, t, 10.0)


def test_registry_ce_fused_linear():
    """Registry entry + valid-mask weighting match the dense path."""
    x, y, t = _problem()
    mask = (jnp.arange(x.shape[0]) % 3) != 0
    fn = L.make_loss("ce_fused_linear", block_n=16, block_c=64)
    loss, _ = fn(x, y, t, valid_mask=mask)
    ref_loss, _ = L.ce(x, y, t, valid_mask=mask)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    assert L.loss_peak_elements("ce_fused_linear", 4096, 262144, 64) \
        == L.loss_peak_elements("ce_fused_linear", 4096, 1 << 30, 64), \
        "fused-linear loss-side peak must be V-independent"


# ---------------------------------------------------------------------------
# Structural (jaxpr) assertion: the (N, V) logits never exist
# ---------------------------------------------------------------------------
def _iter_var_sizes(jaxpr):
    """Every intermediate's element count, recursively including
    sub-jaxprs (scan/cond bodies, pallas_call kernel bodies)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "size"):
                yield int(aval.size)
        for _, val in sorted(eqn.params.items()):
            yield from _iter_param_sizes(val)


def _iter_param_sizes(val):
    if hasattr(val, "eqns"):  # Jaxpr
        yield from _iter_var_sizes(val)
    elif hasattr(val, "jaxpr"):  # ClosedJaxpr
        yield from _iter_var_sizes(val.jaxpr)
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _iter_param_sizes(v)


def _max_intermediate(fn, *args):
    jx = jax.make_jaxpr(fn)(*args)
    return max(_iter_var_sizes(jx.jaxpr), default=0)


def test_jaxpr_no_nv_intermediate():
    """Forward AND backward jaxprs of the fused linear path contain no
    tensor as large as ``N·V`` — nothing bigger than the ``(V, d)``
    table itself. Dense ``ce`` is the positive control proving the
    walker sees such tensors when they exist."""
    x, y, t = _problem(n=64, c=512, d=4)
    n, c, d = 64, 512, 4
    assert n * c > c * d  # shape picked so N·V dominates the table

    g_fused = jax.grad(_kernel_ce_mean, argnums=(0, 1))
    g_dense = jax.grad(_dense_ce_mean, argnums=(0, 1))

    assert _max_intermediate(
        lambda x, y: _kernel_ce_mean(x, y, t), x, y) < n * c
    assert _max_intermediate(lambda x, y: g_fused(x, y, t), x, y) < n * c
    # positive control: the dense path DOES materialize (N, V)
    assert _max_intermediate(
        lambda x, y: g_dense(x, y, t), x, y) >= n * c


def test_jaxpr_sce_kernel_no_candidate_tensor():
    """Kernel-path SCE never materializes the ``(n_b, b_y, d)``
    candidate gather or the ``(n_b, b_x, b_y)`` logits — the jnp path
    (positive control) materializes both."""
    # d large enough that the (n_b, b_y, d) gather dominates the fused
    # path's legitimate scratch (the (n_b, block_c + k) top-k merge row)
    x, y, t = _problem(n=64, c=512, d=16)
    n_b, b_x, b_y = 8, 16, 96
    sizes = (n_b * b_y * 16, n_b * b_x * b_y)
    key = jax.random.PRNGKey(3)

    def make(use_kernel):
        cfg = SCEConfig(n_b, b_x, b_y, use_mix=False, use_kernel=use_kernel)
        def f(x, y):
            return sce_loss(x, y, t, key=key, cfg=cfg)
        return jax.grad(f, argnums=(0, 1))

    fused_max = _max_intermediate(make(True), x, y)
    jnp_max = _max_intermediate(make(False), x, y)
    assert fused_max < min(sizes), (fused_max, sizes)
    assert jnp_max >= max(sizes), (jnp_max, sizes)


# ---------------------------------------------------------------------------
# Exactness limit + softcap kernel-path regression
# ---------------------------------------------------------------------------
def test_sce_exactness_limit_matches_ce():
    """Kernel-path SCE with ``b_x ≥ N`` and ``n_b·b_y ≥ V`` (every
    bucket holds the whole batch and the whole catalog) IS full CE:
    loss, dX and dW match naive ``ce`` and ``ce_fused``."""
    n, c, d = 32, 96, 8
    x, y, t = _problem(n=n, c=c, d=d)
    key = jax.random.PRNGKey(5)
    cfg = SCEConfig(2, n, c, use_mix=False, use_kernel=True)

    def f_sce(x, y):
        return sce_loss(x, y, t, key=key, cfg=cfg)

    def f_ce(x, y):
        return L.ce(x, y, t)[0]

    def f_ce_fused(x, y):
        return L.ce_fused(x, y, t)[0]

    ls, (dxs, dys) = jax.value_and_grad(f_sce, argnums=(0, 1))(x, y)
    lc, (dxc, dyc) = jax.value_and_grad(f_ce, argnums=(0, 1))(x, y)
    lf, (dxf, dyf) = jax.value_and_grad(f_ce_fused, argnums=(0, 1))(x, y)
    np.testing.assert_allclose(ls, lc, rtol=1e-5)
    np.testing.assert_allclose(ls, lf, rtol=1e-5)
    np.testing.assert_allclose(dxs, dxc, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(dys, dyc, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(dxs, dxf, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(dys, dyf, rtol=1e-4, atol=1e-6)


def test_sce_softcap_kernel_path_matches_jnp():
    """Softcapped kernel-path SCE == softcapped jnp-path SCE on loss
    and both grads (the cap is applied inside the gather tile)."""
    x, y, t = _problem(n=64, c=120, d=16, scale=4.0)
    key = jax.random.PRNGKey(1)
    for cap in (None, 10.0):
        mk = lambda uk: SCEConfig(
            8, 16, 32, use_mix=True, use_kernel=uk, logit_softcap=cap)
        f_j = lambda x, y: sce_loss(x, y, t, key=key, cfg=mk(False))
        f_k = lambda x, y: sce_loss(x, y, t, key=key, cfg=mk(True))
        lj, (dxj, dyj) = jax.value_and_grad(f_j, argnums=(0, 1))(x, y)
        lk, (dxk, dyk) = jax.value_and_grad(f_k, argnums=(0, 1))(x, y)
        np.testing.assert_allclose(lk, lj, rtol=1e-5)
        np.testing.assert_allclose(dxk, dxj, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(dyk, dyj, rtol=1e-4, atol=1e-6)


def test_softcap_config_takes_kernel_path(monkeypatch):
    """Regression for the removed ``logit_softcap is None`` gate: a
    softcapped ``use_kernel=True`` config must NOT silently fall back
    to the jnp path. The jnp in-bucket helper is patched to raise —
    the kernel config still evaluates; the jnp config trips the trap."""
    x, y, t = _problem(n=32, c=80, d=8)
    key = jax.random.PRNGKey(2)

    def boom(*a, **k):
        raise AssertionError("jnp in-bucket path used")

    monkeypatch.setattr(sce_lib, "_in_bucket_losses_jnp", boom)
    cfg_k = SCEConfig(4, 8, 16, use_mix=False, use_kernel=True,
                      logit_softcap=30.0)
    loss = sce_loss(x, y, t, key=key, cfg=cfg_k)
    assert jnp.isfinite(loss)
    cfg_j = SCEConfig(4, 8, 16, use_mix=False, use_kernel=False,
                      logit_softcap=30.0)
    with pytest.raises(AssertionError, match="jnp in-bucket path"):
        sce_loss(x, y, t, key=key, cfg=cfg_j)
