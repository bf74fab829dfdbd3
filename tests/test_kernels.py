"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, fwd + grad,
interpret=True on CPU (kernel-taxonomy testing protocol)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# (n_b, b_x, b_y, d) — includes non-divisible tails vs the default blocks
SCE_SHAPES = [
    (1, 8, 16, 8),
    (4, 16, 32, 16),
    (2, 128, 256, 64),
    (3, 100, 200, 32),  # non-divisible everything
    (2, 130, 300, 24),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _sce_problem(key, n_b, b_x, b_y, d, dtype):
    ks = jax.random.split(key, 5)
    x_b = jax.random.normal(ks[0], (n_b, b_x, d), dtype)
    y_b = jax.random.normal(ks[1], (n_b, b_y, d), dtype)
    tgt = jax.random.randint(ks[2], (n_b, b_x), 0, 1000)
    # make some real collisions
    cand = jax.random.randint(ks[3], (n_b, b_y), 0, 1000)
    cand = cand.at[:, 0].set(tgt[:, 0])
    pos = jax.random.normal(ks[4], (n_b, b_x), dtype)
    return x_b, y_b, tgt, cand, pos


@pytest.mark.parametrize("shape", SCE_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sce_bucket_forward(key, shape, dtype):
    args = _sce_problem(key, *shape, dtype)
    got = ops.sce_bucket_loss(*args, interpret=True)
    want = ref.sce_bucket_loss_ref(*args)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("shape", SCE_SHAPES[:3])
def test_sce_bucket_grads(key, shape):
    x_b, y_b, tgt, cand, pos = _sce_problem(key, *shape, jnp.float32)

    def f_kernel(x_b, y_b, pos):
        return jnp.sum(
            ops.sce_bucket_loss(x_b, y_b, tgt, cand, pos, interpret=True)
        )

    def f_ref(x_b, y_b, pos):
        return jnp.sum(ref.sce_bucket_loss_ref(x_b, y_b, tgt, cand, pos))

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(x_b, y_b, pos)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(x_b, y_b, pos)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("n,c,d", [(16, 64, 8), (100, 300, 16),
                                   (256, 1000, 32), (33, 517, 24)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_ce_forward(key, n, c, d, dtype):
    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n, d), dtype)
    y = jax.random.normal(ky, (c, d), dtype)
    t = jax.random.randint(kt, (n,), 0, c)
    got = ops.fused_ce_loss(x, y, t, interpret=True)
    want = ref.fused_ce_loss_ref(x, y, t)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_fused_ce_grads(key):
    n, c, d = 32, 200, 16
    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n, d))
    y = jax.random.normal(ky, (c, d))
    t = jax.random.randint(kt, (n,), 0, c)

    gk = jax.grad(
        lambda x, y: jnp.sum(ops.fused_ce_loss(x, y, t, interpret=True)),
        argnums=(0, 1),
    )(x, y)
    gr = jax.grad(
        lambda x, y: jnp.sum(ref.fused_ce_loss_ref(x, y, t)), argnums=(0, 1)
    )(x, y)
    np.testing.assert_allclose(gk[0], gr[0], rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(gk[1], gr[1], rtol=2e-4, atol=1e-5)


def test_fused_lse_streaming_invariance(key):
    """Block size must not change the result (online-logsumexp exactness)."""
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (40, 16))
    y = jax.random.normal(ky, (333, 16))
    a = ops.fused_lse(x, y, block_n=8, block_c=32, interpret=True)
    b = ops.fused_lse(x, y, block_n=40, block_c=512, interpret=True)
    np.testing.assert_allclose(a, b, rtol=1e-5)


def test_kernel_under_jit(key):
    """pallas_call must compose with jit (the ops are used inside jitted
    train steps)."""
    args = _sce_problem(key, 2, 16, 32, 8, jnp.float32)
    f = jax.jit(lambda *a: ops.sce_bucket_loss(*a, interpret=True))
    got = f(*args)
    want = ref.sce_bucket_loss_ref(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("shape", SCE_SHAPES[:4])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sce_bucket_plse_forward(key, shape, dtype):
    x_b, y_b, tgt, cand, _ = _sce_problem(key, *shape, dtype)
    got = ops.sce_bucket_plse(x_b, y_b, tgt, cand, interpret=True)
    want = ref.sce_bucket_plse_ref(x_b, y_b, tgt, cand)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_sce_bucket_plse_grads(key):
    x_b, y_b, tgt, cand, _ = _sce_problem(key, 3, 32, 48, 16, jnp.float32)

    def f_kernel(x_b, y_b):
        return jnp.sum(
            ops.sce_bucket_plse(x_b, y_b, tgt, cand, interpret=True)
        )

    def f_ref(x_b, y_b):
        return jnp.sum(ref.sce_bucket_plse_ref(x_b, y_b, tgt, cand))

    gk = jax.grad(f_kernel, argnums=(0, 1))(x_b, y_b)
    gr = jax.grad(f_ref, argnums=(0, 1))(x_b, y_b)
    np.testing.assert_allclose(gk[0], gr[0], rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(gk[1], gr[1], rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Scalar-prefetch gather variants (kernels/sce_prefetch.py): candidates
# come as (full Y, idx_y) instead of a materialized y_b
# ---------------------------------------------------------------------------
def _gather_problem(key, n_b, b_x, b_y, d, c, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    x_b = jax.random.normal(ks[0], (n_b, b_x, d), dtype)
    y = jax.random.normal(ks[1], (c, d), dtype)
    idx = jax.random.randint(ks[2], (n_b, b_y), 0, c)
    tgt = jax.random.randint(ks[3], (n_b, b_x), 0, c)
    cand = idx.at[:, 0].set(tgt[:, 0])  # real collisions
    cand = cand.at[:, -1].set(-1)  # and an invalid (masked) slot
    pos = jax.random.normal(ks[4], (n_b, b_x), dtype)
    return x_b, y, idx, tgt, cand, pos


GATHER_SHAPES = [
    (2, 16, 24, 8, 100),
    (3, 100, 50, 16, 257),  # non-divisible everything
    (1, 8, 40, 4, 40),
]


@pytest.mark.parametrize("shape", GATHER_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sce_gather_loss_forward(key, shape, dtype):
    x_b, y, idx, tgt, cand, pos = _gather_problem(key, *shape, dtype)
    got = ops.sce_gather_loss(
        x_b, y, idx, tgt, cand, pos,
        block_bx=16, block_by=16, interpret=True,
    )
    want = ref.sce_bucket_loss_ref(
        x_b, jnp.take(y, idx, axis=0), tgt, cand, pos
    )
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("shape", GATHER_SHAPES[:2])
def test_sce_gather_loss_grads(key, shape):
    """dX streams like the forward; dY accumulates DIRECTLY into the
    (C, d) buffer (no gather-VJP scatter) — must equal the take-path
    oracle's scatter-add, including zero rows for unselected items."""
    x_b, y, idx, tgt, cand, pos = _gather_problem(key, *shape)

    def f_k(x_b, y, pos):
        return jnp.sum(ops.sce_gather_loss(
            x_b, y, idx, tgt, cand, pos,
            block_bx=16, block_by=16, interpret=True,
        ))

    def f_r(x_b, y, pos):
        y_b = jnp.take(y, idx, axis=0)
        return jnp.sum(ref.sce_bucket_loss_ref(x_b, y_b, tgt, cand, pos))

    gk = jax.grad(f_k, argnums=(0, 1, 2))(x_b, y, pos)
    gr = jax.grad(f_r, argnums=(0, 1, 2))(x_b, y, pos)
    assert gk[1].shape == y.shape  # dY comes out catalog-shaped
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)
    # rows never selected (and not a target) must have exactly zero grad
    touched = np.zeros(y.shape[0], bool)
    touched[np.asarray(idx).ravel()] = True
    np.testing.assert_allclose(np.asarray(gk[1])[~touched], 0.0, atol=0)


@pytest.mark.parametrize("shape", GATHER_SHAPES[:2])
def test_sce_gather_plse_matches_ref(key, shape):
    x_b, y, idx, tgt, cand, _ = _gather_problem(key, *shape)
    got = ops.sce_gather_plse(
        x_b, y, idx, tgt, cand, block_bx=16, block_by=16, interpret=True
    )
    want = ref.sce_bucket_plse_ref(
        x_b, jnp.take(y, idx, axis=0), tgt, cand
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    gk = jax.grad(
        lambda y: jnp.sum(ops.sce_gather_plse(
            x_b, y, idx, tgt, cand,
            block_bx=16, block_by=16, interpret=True,
        ))
    )(y)
    gr = jax.grad(
        lambda y: jnp.sum(ref.sce_bucket_plse_ref(
            x_b, jnp.take(y, idx, axis=0), tgt, cand
        ))
    )(y)
    np.testing.assert_allclose(gk, gr, rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("flavour", ["loss", "plse"])
def test_sce_gather_wide_rows(key, flavour):
    """Rows wider than 128 lanes travel through the (C, 1, d) view
    (``sce_prefetch._wide``): forward and both gradients as the
    take-path oracle."""
    x_b, y, idx, tgt, cand, pos = _gather_problem(key, 2, 16, 24, 256, 100)

    def f_k(x_b, y):
        if flavour == "plse":
            return jnp.sum(ops.sce_gather_plse(
                x_b, y, idx, tgt, cand, block_bx=16, block_by=16,
                interpret=True))
        return jnp.sum(ops.sce_gather_loss(
            x_b, y, idx, tgt, cand, pos, block_bx=16, block_by=16,
            interpret=True))

    def f_r(x_b, y):
        y_b = jnp.take(y, idx, axis=0)
        if flavour == "plse":
            return jnp.sum(ref.sce_bucket_plse_ref(x_b, y_b, tgt, cand))
        return jnp.sum(ref.sce_bucket_loss_ref(x_b, y_b, tgt, cand, pos))

    np.testing.assert_allclose(f_k(x_b, y), f_r(x_b, y), rtol=1e-5)
    gk = jax.grad(f_k, argnums=(0, 1))(x_b, y)
    gr = jax.grad(f_r, argnums=(0, 1))(x_b, y)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


def test_sce_gather_duplicate_rows_across_buckets(key):
    """The dY kernel's RMW accumulation: the same catalog row selected
    by SEVERAL buckets must receive the SUM of contributions (the
    revisit case of the gather-indexed output block)."""
    n_b, b_x, d, c = 4, 8, 8, 30
    ks = jax.random.split(key, 4)
    x_b = jax.random.normal(ks[0], (n_b, b_x, d))
    y = jax.random.normal(ks[1], (c, d))
    # every bucket selects the SAME candidate rows → maximal revisiting
    idx = jnp.tile(jnp.arange(12)[None, :], (n_b, 1))
    tgt = jax.random.randint(ks[2], (n_b, b_x), 12, c)  # no collisions
    pos = jax.random.normal(ks[3], (n_b, b_x))

    gk = jax.grad(
        lambda y: jnp.sum(ops.sce_gather_loss(
            x_b, y, idx, tgt, idx, pos,
            block_bx=8, block_by=4, interpret=True,
        ))
    )(y)
    gr = jax.grad(
        lambda y: jnp.sum(ref.sce_bucket_loss_ref(
            x_b, jnp.take(y, idx, axis=0), tgt, idx, pos
        ))
    )(y)
    np.testing.assert_allclose(gk, gr, rtol=2e-4, atol=1e-5)


def test_sce_gather_dy_summed_over_data_shards(key):
    """Under ``shard_map`` with positions on ``data`` and the catalog on
    ``model``, each data shard's backward kernel holds a partial dY.
    The aliased zeros buffer must be typed as varying over ``data`` too,
    so the cotangent is psummed over ``data`` (traced for Mosaic, not
    run: the interpreter cannot run inside ``shard_map``)."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import sce_prefetch

    x_b, y, idx, tgt, cand, _ = _gather_problem(key, 2, 8, 128, 8, 256)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    seen = {}

    def inner(x_b, y, idx, tgt, cand):
        lse = jnp.zeros(tgt.shape, jnp.float32)
        _, dy = sce_prefetch._gbwd(
            x_b, y, idx, tgt, cand, lse, jnp.ones_like(lse),
            block_bx=8, block_by=128, interpret=False,
        )
        seen["dy"] = jax.typeof(dy).vma
        gy = jax.grad(lambda y: jnp.sum(sce_prefetch.sce_gather_plse(
            x_b, y, idx, tgt, cand, 8, 128, False
        )))(y)
        seen["gy"] = jax.typeof(gy).vma
        return jax.lax.psum(jnp.sum(gy), "model")

    fn = jax.shard_map(inner, mesh=mesh, out_specs=P(), in_specs=(
        P("data"), P("model"), P("data"), P("data"), P("data"),
    ))
    jaxpr = str(jax.make_jaxpr(fn)(x_b, y, idx, tgt, cand))
    assert seen["dy"] == {"data", "model"}
    assert seen["gy"] == {"model"}
    assert "psum_invariant[axes=('data',)]" in jaxpr


def test_negative_cand_ids_masked_everywhere(key):
    """The shared invalid-candidate rule (cand_id < 0): sce_bucket
    kernel, prefetch kernel and both refs must agree, and the masked
    slot must contribute no gradient."""
    x_b, y, idx, tgt, cand, pos = _gather_problem(key, 2, 8, 12, 4, 50)
    y_b = jnp.take(y, idx, axis=0)
    a = ref.sce_bucket_loss_ref(x_b, y_b, tgt, cand, pos)
    b = ops.sce_bucket_loss(x_b, y_b, tgt, cand, pos, interpret=True)
    c_ = ops.sce_gather_loss(
        x_b, y, idx, tgt, cand, pos,
        block_bx=8, block_by=8, interpret=True,
    )
    np.testing.assert_allclose(a, b, rtol=1e-5)
    np.testing.assert_allclose(a, c_, rtol=1e-5)
    # vs fully-valid cands: masking the last slot must CHANGE the loss
    cand_all = cand.at[:, -1].set(idx[:, -1])
    d_ = ref.sce_bucket_loss_ref(x_b, y_b, tgt, cand_all, pos)
    assert not np.allclose(np.asarray(a), np.asarray(d_))


def test_union_mode_partials_compose_to_full_lse(key):
    """Merging per-slice partial LSEs reproduces the full logsumexp —
    the union-mode cross-shard merge identity."""
    x_b, y_b, tgt, cand, _ = _sce_problem(key, 2, 16, 64, 8, jnp.float32)
    full = ref.sce_bucket_plse_ref(x_b, y_b, tgt, cand)
    parts = []
    for j in range(4):  # 4 "shards" of 16 candidates
        sl = slice(j * 16, (j + 1) * 16)
        parts.append(
            ref.sce_bucket_plse_ref(x_b, y_b[:, sl], tgt, cand[:, sl])
        )
    stacked = jnp.stack(parts)
    m = jnp.max(stacked, axis=0)
    merged = m + jnp.log(jnp.sum(jnp.exp(stacked - m), axis=0))
    np.testing.assert_allclose(merged, full, rtol=1e-5)
