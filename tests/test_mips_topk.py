"""Streaming MIPS selection (kernels/mips_topk.py + the fused
select_buckets path): bit-exact parity with dense ``lax.top_k`` on
values, ids and tie order, tail/clamp edge cases, fallback routing,
old-vs-new ``select_buckets`` equality, and randomized property-based
differential sweeps over ``(K, block_c, C % block_c, tie density,
valid-mask starvation)`` for both the shared ``topk_merge`` recurrence
and the full kernel — including the selection-sized ``K = b_y`` regime
(ISSUE 4)."""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core.sce import SCEConfig, make_bucket_centers, select_buckets
from repro.kernels import eval_fused as ef
from repro.kernels import eval_topk as et
from repro.kernels import mips_topk as mk
from repro.kernels import ops, ref
from repro.kernels.topk_merge import ID_PAD, buffer_lanes, insert_topk_tile
from _exact import grid_normal

NEG_INF = -1e30

# (n_q, C, d, k, block_q, block_c) — includes C % block_c != 0 tails and
# n_q % block_q != 0 row tails
SHAPES = [
    (8, 100, 16, 10, 4, 32),
    (3, 257, 8, 50, 128, 64),
    (130, 64, 4, 7, 128, 512),
    (5, 1000, 12, 17, 2, 100),
]


def _problem(key, n_q, c, d):
    kq, ky = jax.random.split(key)
    q = grid_normal(kq, (n_q, d))
    y = grid_normal(ky, (c, d))
    return q, y


@pytest.mark.parametrize("shape", SHAPES)
def test_mips_topk_matches_dense(key, shape):
    n_q, c, d, k, bq, bc = shape
    q, y = _problem(key, n_q, c, d)
    want_v, want_i = jax.lax.top_k(q @ y.T, k)
    got_v, got_i = ops.mips_topk(
        q, y, k, block_q=bq, block_c=bc, interpret=True
    )
    ref_v, ref_i = ref.mips_topk_ref(q, y, k, chunk=bc)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(ref_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(want_i))


@pytest.mark.parametrize("shape", SHAPES)
def test_mips_topk_continuous_matches_float64(key, shape):
    """Continuous normals, where summation order shows: kernel and
    chunked reference keep the float64 top-k ids (no near-ties at these
    seeds) and their values agree with float64 to float32 rounding — a
    bfloat16 dot would miss by ~1e-3."""
    n_q, c, d, k, bq, bc = shape
    kq, ky = jax.random.split(key)
    q = jax.random.normal(kq, (n_q, d))
    y = jax.random.normal(ky, (c, d))
    s64 = np.asarray(q, np.float64) @ np.asarray(y, np.float64).T
    want_i = np.argsort(-s64, axis=1, kind="stable")[:, :k]
    want_v = np.take_along_axis(s64, want_i, axis=1)
    scale = np.abs(s64).max()
    for got_v, got_i in (
        ops.mips_topk(q, y, k, block_q=bq, block_c=bc, interpret=True),
        ref.mips_topk_ref(q, y, k, chunk=bc),
    ):
        np.testing.assert_array_equal(np.asarray(got_i), want_i)
        np.testing.assert_allclose(np.asarray(got_v), want_v, rtol=0,
                                   atol=1e-6 * scale)


def test_mips_topk_tie_order(key):
    """Integer-exact embeddings with duplicated catalog rows: ties must
    resolve toward the lower id, exactly the dense lax.top_k rule."""
    kq, ky = jax.random.split(key)
    q = jax.random.randint(kq, (16, 8), -3, 4).astype(jnp.float32)
    y = jax.random.randint(ky, (96, 8), -2, 3).astype(jnp.float32)
    y = y.at[48:].set(y[:48])  # every score appears at least twice
    sc = q @ y.T
    want_v, want_i = jax.lax.top_k(sc, 20)
    got_v, got_i = ops.mips_topk(q, y, 20, block_c=20, interpret=True)
    ref_v, ref_i = ref.mips_topk_ref(q, y, 20, chunk=20)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(want_i))
    # sanity: the duplication actually created cross-half ties
    assert (np.asarray(want_i) >= 48).any()


def test_mips_topk_k_larger_than_catalog(key):
    """b_y > C clamps to C (the oracle's min(b_y, C) clip)."""
    q, y = _problem(key, 6, 40, 8)
    got_v, got_i = ops.mips_topk(q, y, 300, block_c=16, interpret=True)
    assert got_v.shape == (6, 40) and got_i.shape == (6, 40)
    want_v, want_i = jax.lax.top_k(q @ y.T, 40)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_mips_topk_valid_mask(key):
    """The X-side valid_mask: masked rows never selected, same tie rule."""
    q, y = _problem(key, 7, 90, 8)
    vm = jnp.arange(90) % 3 != 0
    want_v, want_i = jax.lax.top_k(
        jnp.where(vm[None, :], q @ y.T, NEG_INF), 12
    )
    got_v, got_i = ops.mips_topk(
        q, y, 12, valid=vm, block_c=32, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_mips_topk_traced_offset_falls_back_to_ref(key, monkeypatch):
    """A traced id_offset (the sharded-catalog case) no longer falls
    back to the chunked reference: the kernel selects local ids and
    ops.mips_topk shifts the real ones by the offset — globally-offset
    ids, same tie order."""
    from repro.kernels import guard

    guard.verdict_for("mips_topk")  # canaries use the reference; run them first

    def no_ref(*a, **k):
        raise AssertionError("traced id_offset routed to the reference")

    monkeypatch.setattr(ref, "mips_topk_ref", no_ref)
    q, y = _problem(key, 4, 64, 8)

    def f(off):
        return ops.mips_topk(q, y, 5, id_offset=off, interpret=True)

    vals, ids = jax.jit(f)(jnp.int32(128))
    want_v, want_i = jax.lax.top_k(q @ y.T, 5)
    # jit fuses the scan matmul differently from the dense one — values
    # may differ by 1 ulp; the selected ids must still match exactly.
    np.testing.assert_allclose(
        np.asarray(vals), np.asarray(want_v), rtol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(ids), np.asarray(want_i) + 128
    )


def test_select_buckets_fused_equals_dense(key):
    """cfg.use_kernel routes selection through mips_topk — ids (and tie
    order) must equal the dense path exactly, with and without
    valid_mask."""
    kx, ky, kb = jax.random.split(key, 3)
    n, c, d = 64, 150, 16
    x = jax.random.normal(kx, (n, d))
    y = jax.random.normal(ky, (c, d))
    cfg_d = SCEConfig(6, 16, 32, use_mix=True, use_kernel=False)
    cfg_k = SCEConfig(6, 16, 32, use_mix=True, use_kernel=True)
    b = make_bucket_centers(kb, x, 6, use_mix=True)
    for vm in (None, jnp.arange(n) < 40):
        ix_d, iy_d = select_buckets(b, x, y, cfg_d, valid_mask=vm)
        ix_k, iy_k = select_buckets(b, x, y, cfg_k, valid_mask=vm)
        np.testing.assert_array_equal(np.asarray(ix_d), np.asarray(ix_k))
        np.testing.assert_array_equal(np.asarray(iy_d), np.asarray(iy_k))


# ---------------------------------------------------------------------------
# Property-based differential sweeps (ISSUE 4 satellite): randomized
# (K, block_c, C % block_c, tie density, valid starvation) vs dense
# lax.top_k — ids, values AND tie order (id equality under exact-float
# ties IS the tie-order assertion).
# ---------------------------------------------------------------------------
def _property_problem(seed, c, d, tie_level, starve):
    """(q, y, valid) with controllable tie density / mask starvation.

    tie_level 0: normals on a 1/8 grid (ties only by coincidence);
    1: small-integer embeddings (exact-float scores, ties everywhere);
    2: integer embeddings + duplicated catalog rows (every score tied).
    starve > 0: valid mask keeps only ``starve`` columns (exercises the
    exhausted-row ID_PAD path when starve < k).
    """
    kq, ky, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    n_q = 5
    if tie_level == 0:
        q = grid_normal(kq, (n_q, d))
        y = grid_normal(ky, (c, d))
    else:
        q = jax.random.randint(kq, (n_q, d), -3, 4).astype(jnp.float32)
        y = jax.random.randint(ky, (c, d), -2, 3).astype(jnp.float32)
        if tie_level == 2 and c >= 2:
            y = y.at[c // 2:].set(y[: c - c // 2])
    if starve:
        order = jax.random.permutation(kv, c)
        valid = jnp.zeros((c,), bool).at[order[:starve]].set(True)
    else:
        valid = None
    return q, y, valid


def _dense_masked_topk(q, y, valid, k):
    scores = q @ y.T
    if valid is not None:
        scores = jnp.where(valid[None, :], scores, NEG_INF)
    return jax.lax.top_k(scores, min(k, y.shape[0]))


def _assert_topk_matches(got_v, got_i, want_v, want_i, valid, k):
    """Exact equality on values and ids; exhausted slots (fewer valid
    columns than k) must carry the ID_PAD placeholder where the dense
    path keeps arbitrary NEG_INF-tied ids."""
    want_v = np.asarray(want_v)
    want_i = np.asarray(want_i)
    got_v = np.asarray(got_v)
    got_i = np.asarray(got_i)
    np.testing.assert_array_equal(got_v, want_v)
    live = want_v > NEG_INF
    np.testing.assert_array_equal(
        np.where(live, got_i, 0), np.where(live, want_i, 0)
    )
    assert (got_i[~live] == ID_PAD).all()


@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    k=st.integers(1, 300),
    c=st.integers(3, 300),
    block_c=st.integers(8, 64),
    tie_level=st.integers(0, 2),
    starve_pct=st.integers(0, 100),
)
@hypothesis.settings(max_examples=12, deadline=None)
def test_mips_topk_ref_property_differential(
    seed, k, c, block_c, tie_level, starve_pct
):
    """Chunked reference vs dense masked lax.top_k across randomized
    K (selection-sized K ≥ C included) / tile size / C-mod-tile tails /
    tie density / mask starvation (starve < k ⇒ placeholder tails)."""
    d = 8
    starve = 0 if starve_pct < 50 else max(1, (starve_pct - 50) * c // 100)
    q, y, valid = _property_problem(seed, c, d, tie_level, starve)
    want_v, want_i = _dense_masked_topk(q, y, valid, k)
    ref_v, ref_i = ref.mips_topk_ref(q, y, k, valid=valid, chunk=block_c)
    _assert_topk_matches(ref_v, ref_i, want_v, want_i, valid, k)


@pytest.mark.slow
@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    k=st.integers(1, 48),
    c=st.integers(3, 300),
    block_c=st.integers(4, 64),
    tie_level=st.integers(0, 2),
    starve_pct=st.integers(0, 100),
)
@hypothesis.settings(max_examples=5, deadline=None)
def test_mips_topk_kernel_property_differential(
    seed, k, c, block_c, tie_level, starve_pct
):
    """The Pallas kernel (interpret mode) over the same randomized
    grid. Slow tier: each interpret call unrolls the K merge rounds,
    ~seconds per example — the fast tier covers the identical property
    through the reference, whose merge shares the tie rule."""
    d = 8
    starve = 0 if starve_pct < 50 else max(1, (starve_pct - 50) * c // 100)
    q, y, valid = _property_problem(seed, c, d, tie_level, starve)
    want_v, want_i = _dense_masked_topk(q, y, valid, k)
    got_v, got_i = ops.mips_topk(
        q, y, k, valid=valid, block_c=block_c, interpret=True
    )
    _assert_topk_matches(got_v, got_i, want_v, want_i, valid, k)


@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    k=st.integers(1, 32),
    n_tiles=st.integers(1, 6),
    tile=st.integers(1, 40),
    tie_level=st.integers(0, 1),
)
@hypothesis.settings(max_examples=8, deadline=None)
def test_merge_topk_tile_property(seed, k, n_tiles, tile, tie_level):
    """The K-round merge oracle in isolation: folding tiles one at a
    time equals one dense lax.top_k over the whole concatenation —
    values, ids, tie order, placeholder slots."""
    rng = np.random.default_rng(seed)
    rows, width = 4, n_tiles * tile
    if tie_level:
        scores = rng.integers(-3, 4, size=(rows, width)).astype(np.float32)
    else:
        scores = rng.normal(size=(rows, width)).astype(np.float32)
    # random NEG_INF holes so some rows can exhaust below k
    scores[rng.random((rows, width)) < 0.2] = NEG_INF

    vals = jnp.full((rows, k), NEG_INF, jnp.float32)
    ids = jnp.full((rows, k), ID_PAD, jnp.int32)
    for t in range(n_tiles):
        tile_scores = jnp.asarray(scores[:, t * tile:(t + 1) * tile])
        tile_ids = jnp.broadcast_to(
            t * tile + jnp.arange(tile, dtype=jnp.int32)[None, :],
            tile_scores.shape,
        )
        vals, ids = ref.merge_topk_tile(vals, ids, tile_scores, tile_ids, k)

    want_v, want_i = jax.lax.top_k(jnp.asarray(scores), min(k, width))
    pad = k - min(k, width)
    if pad:  # buffer wider than the data: dense oracle covers the head
        vals, ids = vals[:, :width], ids[:, :width]
    _assert_topk_matches(vals, ids, want_v, want_i, None, k)


@pytest.mark.slow
def test_mips_topk_kernel_selection_sized_k():
    """The selection-sized K = b_y = 256 regime (ROADMAP flags the
    K-round merge as unprofiled there): the kernel recurrence must stay
    exact — ids, values, tie order — at production bucket size, with a
    C % block tail and tie-heavy integer scores. (The reference covers
    the same regime across random draws in the fast property sweep.)"""
    k, c, d, block_c = 256, 600, 8, 128
    q, y, _ = _property_problem(7, c, d, tie_level=1, starve=0)
    want_v, want_i = _dense_masked_topk(q, y, None, k)
    got_v, got_i = ops.mips_topk(q, y, k, block_c=block_c, interpret=True)
    _assert_topk_matches(got_v, got_i, want_v, want_i, None, k)
    ref_v, ref_i = ref.mips_topk_ref(q, y, k, chunk=block_c)
    _assert_topk_matches(ref_v, ref_i, want_v, want_i, None, k)


# ---------------------------------------------------------------------------
# The insertion merge (topk_merge.insert_topk_tile) that the streaming
# top-k kernels run: differential against the K-round oracle
# (ref.merge_topk_tile) and dense lax.top_k.
# ---------------------------------------------------------------------------
def _insert_fold(scores, k, tile):
    """Fold ``(rows, width)`` scores tile by tile through
    ``insert_topk_tile`` in an interpret-mode Pallas kernel (the merge's
    lane shift is a Pallas TPU primitive), with the buffer at a multiple
    of 128 lanes as the kernels keep it. Returns the first ``k``
    slots."""
    rows, width = scores.shape
    n_t = -(-width // tile)
    lanes = buffer_lanes(k)
    sp = np.full((rows, n_t * tile), NEG_INF, np.float32)
    sp[:, :width] = scores

    def kernel(s_ref, v_ref, i_ref):
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _init():
            v_ref[...] = jnp.full_like(v_ref, NEG_INF)
            i_ref[...] = jnp.full_like(i_ref, ID_PAD)

        v_ref[...], i_ref[...] = insert_topk_tile(
            v_ref[...], i_ref[...], s_ref[...], j * tile, k
        )

    vals, ids = pl.pallas_call(
        kernel,
        grid=(n_t,),
        in_specs=[pl.BlockSpec((rows, tile), lambda j: (0, j))],
        out_specs=[
            pl.BlockSpec((rows, lanes), lambda j: (0, 0)),
            pl.BlockSpec((rows, lanes), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        ],
        interpret=True,
    )(jnp.asarray(sp))
    return vals[:, :k], ids[:, :k]


def _rounds_fold(scores, k, tile):
    """The same fold through the K-round oracle ``ref.merge_topk_tile``."""
    rows, width = scores.shape
    vals = jnp.full((rows, k), NEG_INF, jnp.float32)
    ids = jnp.full((rows, k), ID_PAD, jnp.int32)
    for t0 in range(0, width, tile):
        tv = jnp.asarray(scores[:, t0:t0 + tile])
        ti = jnp.broadcast_to(
            t0 + jnp.arange(tv.shape[1], dtype=jnp.int32)[None, :], tv.shape
        )
        vals, ids = ref.merge_topk_tile(vals, ids, tv, ti, k)
    return vals, ids


def _merge_scores(seed, rows, width):
    """Scores on a 1/8 grid (dense ties), 20% ``NEG_INF`` holes, and two
    valid-starved rows that keep only a handful of scores. Adding 0
    turns rounding's ``-0.0`` into ``0.0``: ``lax.top_k`` orders the
    two zeros, the merges' comparisons do not."""
    rng = np.random.default_rng(seed)
    scores = (np.round(rng.normal(size=(rows, width)) * 8) / 8).astype(
        np.float32) + np.float32(0)
    scores[rng.random((rows, width)) < 0.2] = NEG_INF
    scores[1, 3:] = NEG_INF
    scores[-1, rng.random(width) < 0.95] = NEG_INF
    return scores


@pytest.mark.parametrize("k,tile,n_tiles", [
    (10, 37, 5),
    (64, 48, 4),
    (256, 128, 4),
    (320, 256, 3),
    (64, 16, 7),   # K > tile: several tiles before the buffer is full
])
def test_insert_merge_equals_rounds_merge(k, tile, n_tiles):
    """The insertion merge leaves exactly the K-round merge's buffer —
    values, ids, tie order, ``ID_PAD`` exhausted slots — and both equal
    a dense ``lax.top_k``, across tie-dense grid scores, ``NEG_INF``
    holes and valid-starved rows."""
    width = tile * n_tiles - tile // 3  # a short last tile
    scores = _merge_scores(k * 1000 + tile, 6, width)
    got_v, got_i = _insert_fold(scores, k, tile)
    want_v, want_i = _rounds_fold(scores, k, tile)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    dense_v, dense_i = jax.lax.top_k(jnp.asarray(scores), min(k, width))
    _assert_topk_matches(got_v, got_i, dense_v, dense_i, None, k)


@pytest.mark.parametrize("k,c,block_c,starve", [
    (10, 700, 128, 0),
    (64, 600, 128, 40),    # valid-starved: 40 selectable rows < k
    (256, 900, 128, 0),
    (320, 1100, 256, 0),
    (100, 300, 32, 0),     # K > block_c
])
def test_mips_topk_insert_kernel_matches_dense(k, c, block_c, starve):
    """The kernel at eval-, selection- and position-sized K, K above the
    tile width and valid-starved catalogs: values, ids and tie order
    equal the dense masked ``lax.top_k`` and the chunked reference."""
    q, y, valid = _property_problem(k + c, c, 8, tie_level=1, starve=starve)
    want_v, want_i = _dense_masked_topk(q, y, valid, k)
    got_v, got_i = mk.mips_topk(
        q, y, k, valid=valid, block_c=block_c, interpret=True
    )
    _assert_topk_matches(got_v, got_i, want_v, want_i, valid, k)
    ref_v, ref_i = ref.mips_topk_ref(q, y, k, valid=valid, chunk=block_c)
    _assert_topk_matches(ref_v, ref_i, want_v, want_i, valid, k)


def _monotone_problem(c, d, ascending):
    """A catalog whose scores rise (or fall) with the item id for every
    query row: ``y_i = t_i · u`` on a 1/8 grid with every ``q · u > 0``."""
    t = np.arange(1, c + 1, dtype=np.float32) / 8
    if not ascending:
        t = t[::-1].copy()
    y = np.zeros((c, d), np.float32)
    y[:, 0] = t
    rng = np.random.default_rng(c)
    q = (np.round(rng.normal(size=(6, d)) * 8) / 8).astype(np.float32)
    q[:, 0] = np.asarray([1, 2, 0.5, 3, 0.25, 1.5], np.float32)
    return jnp.asarray(q), jnp.asarray(y)


@pytest.mark.parametrize("ascending", [True, False])
def test_mips_topk_sorted_catalog(ascending):
    """A catalog sorted by score ascending is the merge's worst case —
    every tile beats the whole buffer, so each inserts ``min(K, tile)``
    entries; sorted descending, no tile inserts any once the buffer is
    full (during the first tile when K <= tile, the second when K >
    tile). Both stay exact."""
    c = 640
    q, y = _monotone_problem(c, 8, ascending)
    for k, block_c in ((96, 64), (40, 128)):
        want_v, want_i = _dense_masked_topk(q, y, None, k)
        got_v, got_i = mk.mips_topk(q, y, k, block_c=block_c,
                                    interpret=True)
        _assert_topk_matches(got_v, got_i, want_v, want_i, None, k)


@pytest.mark.parametrize("kernel", ["eval_fused", "eval_topk"])
@pytest.mark.parametrize("ascending", [True, False])
def test_eval_kernels_sorted_catalog(kernel, ascending):
    """The eval kernels run the same insertion merge: on a catalog
    sorted by score ascending (every tile inserts ``K``) and descending
    (none does once the buffer is full), at eval's ``K = 10`` with
    ``K`` above the tile width too, their top-k equals the dense
    ``lax.top_k`` and their rank counts the dense counts."""
    c, block_c = 640, 64
    q, y = _monotone_problem(c, 8, ascending)
    targets = jnp.arange(q.shape[0], dtype=jnp.int32) * 97
    scores = np.asarray(q @ y.T)
    tgt = scores[np.arange(q.shape[0]), np.asarray(targets)]
    for k in (10, 96):
        want_v, want_i = _dense_masked_topk(q, y, None, k)
        if kernel == "eval_fused":
            got_v, got_i, gt, eq, *_ = ef.eval_fused(
                q, y, targets, k, block_c=block_c, interpret=True
            )
        else:
            got_v, got_i, gt, eq = et.eval_topk(
                q, y, jnp.asarray(tgt), k, block_c=block_c, interpret=True
            )
        _assert_topk_matches(got_v, got_i, want_v, want_i, None, k)
        np.testing.assert_array_equal(
            np.asarray(gt), (scores > tgt[:, None]).sum(1))
        np.testing.assert_array_equal(
            np.asarray(eq), (scores == tgt[:, None]).sum(1))


def test_mips_topk_exhausted_rows_use_placeholder(key):
    """Fewer valid columns than k: the trailing slots carry NEG_INF
    values and the INT32_MAX placeholder id, like the reference."""
    q, y = _problem(key, 3, 20, 4)
    vm = jnp.arange(20) < 5  # only 5 selectable rows
    got_v, got_i = ops.mips_topk(
        q, y, 8, valid=vm, block_c=7, interpret=True
    )
    ref_v, ref_i = ref.mips_topk_ref(q, y, 8, valid=vm, chunk=7)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))
    assert (np.asarray(got_i)[:, 5:] == np.iinfo(np.int32).max).all()
    assert (np.asarray(got_v)[:, 5:] == NEG_INF).all()
