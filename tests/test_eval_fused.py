"""Fused single-pass eval scorer (ISSUE 5 tentpole): one catalog sweep
must reproduce the two-pass ``eval_tgt_scores`` → ``eval_topk`` oracle
BIT-FOR-BIT (ranks, ids, tie order, target scores) — including
tie-heavy integer cases and ``C % block != 0`` tails — and its
online-LSE carry must match ``ce_chunked`` / dense ``logsumexp``
within f32 fold tolerance (bitwise, at constructed exactly-foldable
logits). Plus: the bitwise target-gather pin (the property the whole
design rests on), the empty-batch / starved-k edges, the memory-model
acceptance, and the grep-guard asserting the deprecated two-pass
entries have no production caller left. The dp×tp mesh variants live
in tests/test_distributed.py."""
import os
import re
import warnings

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.eval import ranks_from_counts, streaming_eval_scores
from repro.kernels import ops, ref
from _exact import grid_normal

REPO = os.path.join(os.path.dirname(__file__), "..")


def _two_pass(x, y, t, k, *, block_c, c_lo=1, c_hi=None, kernel=False):
    """The deprecated two-pass oracle, warnings silenced (this file is
    its one sanctioned caller besides the bench comparison)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        if kernel:
            tgt = ops.eval_tgt_scores(x, y, t, block_c=block_c,
                                      interpret=True)
            out = ops.eval_topk(x, y, tgt, k, block_c=block_c,
                                c_lo=c_lo, c_hi=c_hi, interpret=True)
        else:
            tgt = ref.eval_tgt_scores_ref(x, y, t, chunk=block_c)
            out = ref.eval_topk_ref(x, y, tgt, k, chunk=block_c,
                                    c_lo=c_lo, c_hi=c_hi)
    return out + (tgt,)


def _problem(seed, b, c, d, tie_level):
    rng = np.random.default_rng(seed)
    if tie_level:
        x = rng.integers(-3, 4, (b, d)).astype(np.float32)
        y = rng.integers(-2, 3, (c, d)).astype(np.float32)
        if tie_level > 1 and c >= 2:  # duplicated rows → exact ties
            y[c // 2:] = y[: c - c // 2]
    else:
        x = rng.normal(size=(b, d)).astype(np.float32)
        y = rng.normal(size=(c, d)).astype(np.float32)
    t = rng.integers(1, c, (b,)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y), jnp.asarray(t)


# ---------------------------------------------------------------------------
# The bitwise pin the design rests on
# ---------------------------------------------------------------------------
@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    b=st.integers(1, 70),
    c=st.integers(2, 400),
    block_c=st.integers(4, 96),
)
@hypothesis.settings(max_examples=12, deadline=None)
def test_tgt_gather_bitwise_equals_swept_column(seed, b, c, block_c):
    """``eval_tgt_gather`` must equal the deprecated full-sweep
    ``eval_tgt_scores`` BITWISE on generic floats — the same-shape-gemm
    determinism the fused design rests on (a gather-einsum fails this
    on ~15–25%% of rows). Random B/C/tile incl. B > block_c (several
    gather tiles) and C %% block != 0."""
    x, y, t = _problem(seed, b, c, 16, tie_level=0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        want = ref.eval_tgt_scores_ref(x, y, t, chunk=block_c)
    got = ref.eval_tgt_gather_ref(x, y, t, chunk=block_c)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tgt_gather_kernel_bitwise_and_sharded_assembly(key):
    """Kernel path of the same pin, plus the shard contract: per-slice
    gathers (id_offset, out-of-range targets → 0) must sum to the
    full-catalog value exactly."""
    b, c, d, bc = 33, 210, 16, 64
    x, y, t = _problem(3, b, c, d, tie_level=0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        want = ops.eval_tgt_scores(x, y, t, block_c=bc, interpret=True)
    got = ops.eval_tgt_gather(x, y, t, block_c=bc, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    half = c // 2
    lo = ops.eval_tgt_gather(x, y[:half], t, block_c=bc, interpret=True)
    hi = ops.eval_tgt_gather(x, y[half:], t, block_c=bc,
                             id_offset=half, interpret=True)
    # each target is owned by exactly one slice; the other contributes 0
    np.testing.assert_array_equal(
        np.asarray(lo) + np.asarray(hi), np.asarray(got)
    )
    assert (np.asarray(lo) * np.asarray(hi) == 0).all()


# ---------------------------------------------------------------------------
# Fused vs two-pass, bit-for-bit
# ---------------------------------------------------------------------------
@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    b=st.integers(1, 48),
    c=st.integers(2, 300),
    k=st.integers(1, 40),
    block_c=st.integers(4, 80),
    tie_level=st.integers(0, 2),
)
@hypothesis.settings(max_examples=12, deadline=None)
def test_fused_matches_two_pass_property(seed, b, c, k, block_c, tie_level):
    """The ISSUE 5 acceptance property: the fused single sweep equals
    the two-pass oracle bit-for-bit on (vals, ids, gt, eq, tgt) across
    randomized shapes, tile sizes, C %% block tails and tie densities
    (integer-exact embeddings with duplicated rows at tie_level=2)."""
    x, y, t = _problem(seed, b, c, 16, tie_level)
    want = _two_pass(x, y, t, k, block_c=block_c)
    got = ref.eval_fused_ref(x, y, t, k, chunk=block_c, c_lo=1,
                             with_lse=True)
    for g, w, name in zip(got[:4], want[:4], ["vals", "ids", "gt", "eq"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(want[4]),
                                  err_msg="tgt")
    # the target's own column is always seen → eq ≥ 1 (valid targets)
    assert int(np.asarray(got[3]).min()) >= 1


@pytest.mark.parametrize("shape", [
    (8, 64, 16, 5, 4, 16),
    (33, 517, 24, 10, 16, 128),  # non-divisible everything
    (16, 300, 8, 7, 128, 512),  # blocks clamp to full extents
])
def test_fused_kernel_matches_two_pass_kernel(key, shape):
    """The Pallas kernel path (interpret mode) over the ISSUE 2
    acceptance grid: fused kernel == two-pass kernels == fused ref,
    bitwise, plus the LSE carry vs dense logsumexp."""
    b, c, d, k, bb, bc = shape
    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, d))
    y = jax.random.normal(ky, (c, d))
    t = jax.random.randint(kt, (b,), 1, c)
    want = _two_pass(x, y, t, k, block_c=bc, kernel=True)
    got = ops.eval_fused(x, y, t, k, block_b=bb, block_c=bc, c_lo=1,
                         with_lse=True, interpret=True)
    gotr = ref.eval_fused_ref(x, y, t, k, chunk=bc, c_lo=1, with_lse=True)
    for g, r, w, name in zip(got[:5], gotr[:5], want[:4] + (want[4],),
                             ["vals", "ids", "gt", "eq", "tgt"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg="ref-" + name)
    scores = np.array(x @ y.T, np.float32)
    scores[:, 0] = -np.inf
    want_lse = np.asarray(jax.nn.logsumexp(jnp.asarray(scores), axis=-1))
    for gg in (got, gotr):
        lse = np.asarray(gg[5]) + np.log(np.asarray(gg[6]))
        np.testing.assert_allclose(lse, want_lse, rtol=2e-6, atol=2e-6)


def test_fused_tie_heavy_exact(key):
    """Integer-exact duplicated-row catalog: ties everywhere, and the
    fused path must still match the two-pass oracle AND the dense
    pessimistic ranks exactly."""
    from repro.core import metrics as core_metrics

    b, c, d, k = 24, 96, 8, 10
    x, y, t = _problem(11, b, c, d, tie_level=2)
    want = _two_pass(x, y, t, k, block_c=32)
    got = ops.eval_fused(x, y, t, k, block_c=32, c_lo=1, interpret=True)
    for g, w, name in zip(got[:4], want[:4], ["vals", "ids", "gt", "eq"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    eq = np.asarray(got[3])
    assert (eq > 1).any(), "tie-heavy case produced no target ties"
    scores = np.array(x @ y.T)
    scores[:, 0] = -1e30
    oracle = np.asarray(core_metrics.rank_of_target(
        jnp.asarray(scores), jnp.asarray(t)
    ))
    np.testing.assert_array_equal(ranks_from_counts(got[2], eq), oracle)


def test_fused_edge_cases(key):
    """B = 0 empties (incl. the LSE slots) and k exceeding the valid
    column count (placeholder tails) — both bit-equal to the oracle."""
    ky = jax.random.fold_in(key, 1)
    y = jax.random.normal(ky, (32, 8))
    out = ops.eval_fused(jnp.zeros((0, 8)), y, jnp.zeros((0,), jnp.int32),
                         5, with_lse=True, interpret=True)
    assert out[0].shape == (0, 5) and out[1].shape == (0, 5)
    assert all(o.shape == (0,) for o in out[2:])

    b, c, d, k = 6, 6, 8, 5
    kx, kt = jax.random.split(key)
    x = grid_normal(kx, (b, d))
    y2 = grid_normal(ky, (c, d))
    t = jax.random.randint(kt, (b,), 1, 4)
    want = _two_pass(x, y2, t, k, block_c=2, c_lo=1, c_hi=4)
    got = ops.eval_fused(x, y2, t, k, block_c=2, c_lo=1, c_hi=4,
                         interpret=True)
    for g, w, name in zip(got[:4], want[:4], ["vals", "ids", "gt", "eq"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    assert (np.asarray(got[1])[:, 3:] == np.iinfo(np.int32).max).all()


@pytest.mark.parametrize("shape", [(6, 6, 8, 5, 2, 1, 4),
                                   (33, 517, 16, 10, 128, 1, 517),
                                   (12, 300, 64, 10, 128, 1, 290)])
def test_fused_counts_match_float64_on_continuous_inputs(key, shape):
    """Rank counts on continuous normals against float64: ``gt`` counts
    the items scoring strictly above the target, never the target
    itself. The kernel and ``eval_fused_ref`` both hold this (the
    first shape is the case on which the deprecated two-pass XLA
    oracle counted the target above itself in 3 of 6 rows: its sweep
    scored the target's column one ulp above its own extracted target
    score)."""
    b, c, d, k, bc, c_lo, c_hi = shape
    kx, ky, kt = jax.random.split(jax.random.fold_in(key, c), 3)
    x = jax.random.normal(kx, (b, d))
    y = jax.random.normal(ky, (c, d))
    t = jax.random.randint(kt, (b,), c_lo, c_hi)
    s64 = np.asarray(x, np.float64) @ np.asarray(y, np.float64).T
    own = s64[np.arange(b), np.asarray(t)][:, None]
    want_gt = np.sum(s64[:, c_lo:c_hi] > own, axis=1)
    for got in (
        ops.eval_fused(x, y, t, k, block_c=bc, c_lo=c_lo, c_hi=c_hi,
                       interpret=True),
        ref.eval_fused_ref(x, y, t, k, chunk=bc, c_lo=c_lo, c_hi=c_hi),
    ):
        np.testing.assert_array_equal(np.asarray(got[2]), want_gt)
        np.testing.assert_array_equal(np.asarray(got[3]), np.ones(b))


def test_streaming_front_end_impls_agree(key):
    """`streaming_eval_scores` impl="ref" vs impl="kernel" — identical
    (vals, ids, gt, eq, tgt) and f32-close LSE."""
    b, c, d, k = 16, 517, 16, 10
    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, d))
    y = jax.random.normal(ky, (c, d))
    t = jax.random.randint(kt, (b,), 1, c)
    a = streaming_eval_scores(x, y, t, k, block_c=128, c_lo=1,
                              impl="ref", with_lse=True)
    bk = streaming_eval_scores(x, y, t, k, block_c=128, c_lo=1,
                               impl="kernel", interpret=True,
                               with_lse=True)
    for g, w in zip(a[:5], bk[:5]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_allclose(
        np.asarray(a[5]) + np.log(np.asarray(a[6])),
        np.asarray(bk[5]) + np.log(np.asarray(bk[6])),
        rtol=1e-6,
    )


# ---------------------------------------------------------------------------
# Online-LSE carry properties
# ---------------------------------------------------------------------------
@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    c=st.integers(2, 200),
    n_dup=st.integers(1, 30),
)
@hypothesis.settings(max_examples=10, deadline=None)
def test_lse_fold_order_invariant_at_exact_logits(seed, c, n_dup):
    """Chunking/fold-order invariance at integer-exact logits: when
    every row's max is duplicated ``n_dup`` times and all other logits
    sit ≥ 200 below it (their f32 ``exp`` underflows to exactly 0),
    the carry fold is exact — so ``lse`` must equal
    ``max + log(n_dup)`` BITWISE for every chunking, and hence be
    identical across chunk sizes."""
    rng = np.random.default_rng(seed)
    b, d = 5, 1
    n_dup = min(n_dup, c)
    # x = 1 ⇒ logits = y broadcast: exact control of every logit
    x = jnp.ones((b, d), jnp.float32)
    vals = rng.integers(-250, -201, size=c).astype(np.float32)
    top = float(rng.integers(0, 5))
    pos = rng.choice(c, size=n_dup, replace=False)
    vals[pos] = top
    y = jnp.asarray(vals[:, None])
    t = jnp.full((b,), int(pos.min()), jnp.int32)

    want = np.float32(top) + np.log(np.float32(n_dup))
    lses = []
    for chunk in (1, 3, 7, c, max(c // 2, 1)):
        out = ref.eval_fused_ref(x, y, t, 1, chunk=chunk, with_lse=True)
        lse = np.asarray(out[5]) + np.log(np.asarray(out[6]))
        np.testing.assert_array_equal(lse, np.full(b, want, np.float32))
        lses.append(lse)
    dense = np.asarray(jax.nn.logsumexp(jnp.asarray(x @ y.T), axis=-1))
    np.testing.assert_array_equal(lses[0], dense)


@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    c=st.integers(2, 120),
    chunk=st.integers(1, 40),
)
@hypothesis.settings(max_examples=10, deadline=None)
def test_lse_fold_close_at_generic_logits(seed, c, chunk):
    """Generic floats: the carry fold across any chunking matches dense
    ``logsumexp`` to f32 rounding (the fold is exact only when the
    partial sums are — the constructed case above pins that; here the
    guarantee is the usual online-softmax error bound)."""
    x, y, t = _problem(seed, 6, c, 8, tie_level=0)
    out = ref.eval_fused_ref(x, y, t, 1, chunk=chunk, with_lse=True)
    lse = np.asarray(out[5]) + np.log(np.asarray(out[6]))
    dense = np.asarray(jax.nn.logsumexp(
        jnp.asarray(x @ y.T), axis=-1
    ))
    np.testing.assert_allclose(lse, dense, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("cap", [None, 30.0])
def test_fused_nll_matches_ce_chunked(key, cap):
    """The LM wiring identity: ``lse − softcap(tgt)`` from the fused
    sweep over ``[1, V)`` equals ``ce_chunked`` over ``y[1:V]`` within
    f32 carry tolerance, softcap applied inside the tile on both
    sides."""
    from repro.core.losses import ce_chunked
    from repro.core.sce import apply_softcap

    b, c, d = 40, 333, 16
    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, d)) * 3  # scale where a 30.0 cap bites
    y = jax.random.normal(ky, (c, d)) * 3
    t = jax.random.randint(kt, (b,), 1, c)
    out = ref.eval_fused_ref(x, y, t, 1, chunk=64, c_lo=1, c_hi=c,
                             logit_softcap=cap, with_lse=True)
    lse = np.asarray(out[5]) + np.log(np.asarray(out[6]))
    nll = lse - np.asarray(apply_softcap(jnp.asarray(out[4]), cap))
    want, _ = ce_chunked(x, y[1:], t - 1, chunk_size=64, logit_softcap=cap)
    np.testing.assert_allclose(nll.mean(), float(want), rtol=1e-5)


def test_eval_memory_model_unchanged():
    """ISSUE 5 acceptance: fusing the sweeps did not grow the peak —
    the model is still ``B·(block + 2K + 2)`` (the LM variant
    ``B·T·(block + 2K + 4)``), i.e. no worse than the two-pass path's
    peak pass."""
    from repro.eval import eval_peak_elements, lm_eval_peak_elements

    assert eval_peak_elements(512, 10, 512) == 512 * (512 + 2 * 10 + 2)
    assert lm_eval_peak_elements(32, 64, 10, 512) == (
        32 * 64 * (512 + 2 * 10 + 4)
    )


# ---------------------------------------------------------------------------
# Deprecation guard
# ---------------------------------------------------------------------------
def test_two_pass_entry_points_warn():
    """The retained oracle entries must be LOUD about their status."""
    x = jnp.ones((2, 4))
    y = jnp.ones((6, 4))
    t = jnp.zeros((2,), jnp.int32)
    with pytest.warns(DeprecationWarning, match="two-pass"):
        tgt = ops.eval_tgt_scores(x, y, t, interpret=True)
    with pytest.warns(DeprecationWarning, match="two-pass"):
        ops.eval_topk(x, y, tgt, 2, interpret=True)


def test_grep_guard_no_production_two_pass_callers():
    """ISSUE 5 satellite: no production call site of the deprecated
    two-pass entries remains. Allowed referrers: the kernels package
    itself (definitions + the ops/ref oracle layer), tests, and the
    eval-pipeline benchmark (which times the oracle AGAINST the fused
    path — a differential use, explicitly allowlisted)."""
    # call sites only — prose/docstring mentions of the oracle are fine
    pattern = re.compile(
        r"\beval_tgt_scores(?:_ref)?\s*\(|\beval_topk(?:_ref)?\s*\("
    )
    allowed = {
        os.path.normpath(os.path.join("benchmarks", "kernel_bench.py")),
    }
    offenders = []
    for root in ("src", "benchmarks", "examples"):
        for dirpath, _dirs, files in os.walk(os.path.join(REPO, root)):
            if os.path.join("repro", "kernels") in dirpath:
                continue  # the oracle's home
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                rel = os.path.normpath(os.path.relpath(path, REPO))
                if rel in allowed:
                    continue
                with open(path, encoding="utf-8") as f:
                    for ln, line in enumerate(f, 1):
                        if pattern.search(line):
                            offenders.append(f"{rel}:{ln}: {line.strip()}")
    assert not offenders, (
        "deprecated two-pass eval entries still have production "
        "callers:\n" + "\n".join(offenders)
    )
