"""repro.eval equality chain: Pallas streaming kernel vs the chunked
pure-jnp reference vs the dense ``core.metrics`` oracle — exact (not
allclose) on ranks, ids and metrics, including tie-heavy and
non-divisible padded-tail cases (ISSUE 2 acceptance grid). The dp×tp
mesh variants live in tests/test_distributed.py. The two-pass scorer exercised here is
the deprecated differential oracle (PR 5) — its DeprecationWarning is
expected and silenced for the whole module."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import metrics as core_metrics
from repro.eval import (
    MetricAccumulator,
    dense_eval_elements,
    eval_peak_elements,
    evaluate_streaming,
    ranks_from_counts,
    streaming_rank_topk,
)
from repro.kernels import ops, ref
from _exact import grid_normal

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

# (B, C, d, k, block_b, block_c) — includes C % block_c != 0 tails and
# a block_b that doesn't divide B
EVAL_SHAPES = [
    (8, 64, 16, 5, 4, 16),
    (33, 517, 24, 10, 16, 128),  # non-divisible everything
    (16, 300, 8, 7, 128, 512),  # blocks clamp to full extents
    (64, 1000, 32, 10, 32, 256),
]


def _dense_oracle(x, y, targets, *, c_lo=1):
    """(scores, ranks, top_ids) from the materializing path, with the
    same pessimistic tie rank as core.metrics.rank_of_target."""
    scores = np.array(jnp.asarray(x) @ jnp.asarray(y).T)
    scores[:, :c_lo] = -1e30
    ranks = np.asarray(
        core_metrics.rank_of_target(
            jnp.asarray(scores), jnp.asarray(targets)
        )
    )
    return scores, ranks


@pytest.mark.parametrize("shape", EVAL_SHAPES)
def test_eval_topk_kernel_vs_ref_vs_dense(key, shape):
    b, c, d, k, bb, bc = shape
    kx, ky, kt = jax.random.split(key, 3)
    x = grid_normal(kx, (b, d))
    y = grid_normal(ky, (c, d))
    t = jax.random.randint(kt, (b,), 1, c)

    tgt_k = ops.eval_tgt_scores(x, y, t, block_b=bb, block_c=bc,
                                interpret=True)
    got = ops.eval_topk(x, y, tgt_k, k, block_b=bb, block_c=bc,
                        c_lo=1, interpret=True)
    tgt_r = ref.eval_tgt_scores_ref(x, y, t, chunk=bc)
    want = ref.eval_topk_ref(x, y, tgt_r, k, chunk=bc, c_lo=1)
    for g, w, name in zip(got, want, ["vals", "ids", "gt", "eq"]):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=name
        )

    # exact parity with the dense oracle: top-k selection (incl. tie
    # order: lower id wins) and pessimistic ranks
    scores, oracle_ranks = _dense_oracle(x, y, t)
    dv, di = jax.lax.top_k(jnp.asarray(scores), k)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(di))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(dv))
    np.testing.assert_array_equal(
        ranks_from_counts(got[2], got[3]), oracle_ranks
    )
    # the target's own column must always be seen (bitwise-consistent
    # target extraction — the reason eval_tgt_scores exists)
    assert int(np.asarray(got[3]).min()) >= 1


def test_eval_topk_tie_heavy_exact(key):
    """Integer-representable embeddings (exact float arithmetic in any
    summation order) with many duplicated catalog rows — score ties are
    everywhere and every path must agree exactly."""
    b, c, d, k = 24, 96, 8, 10
    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.randint(kx, (b, d), -3, 4).astype(jnp.float32)
    y = jax.random.randint(ky, (c, d), -2, 3).astype(jnp.float32)
    # duplicate blocks of rows → guaranteed exact column ties
    y = y.at[c // 2:].set(y[: c - c // 2])
    t = jax.random.randint(kt, (b,), 1, c)

    tgt = ops.eval_tgt_scores(x, y, t, block_c=32, interpret=True)
    got = ops.eval_topk(x, y, tgt, k, block_c=32, c_lo=1, interpret=True)
    want = ref.eval_topk_ref(
        x, y, ref.eval_tgt_scores_ref(x, y, t, chunk=32),
        k, chunk=32, c_lo=1,
    )
    for g, w, name in zip(got, want, ["vals", "ids", "gt", "eq"]):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=name
        )

    scores, oracle_ranks = _dense_oracle(x, y, t)
    # the construction must actually produce target ties
    eq = np.asarray(got[3])
    assert (eq > 1).any(), "tie-heavy case produced no target ties"
    np.testing.assert_array_equal(
        ranks_from_counts(got[2], eq), oracle_ranks
    )
    dv, di = jax.lax.top_k(jnp.asarray(scores), k)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(di))

    # full-metric parity under ties, COV included: topk_metrics' stable
    # argsort must reproduce the streaming lower-id tie rule
    oracle = core_metrics.topk_metrics(scores, np.asarray(t), catalog=c)
    acc = MetricAccumulator((1, 5, 10), c)
    acc.update(ranks_from_counts(got[2], eq), np.asarray(got[1]))
    assert acc.result() == pytest.approx(oracle, abs=1e-12)


def test_eval_topk_empty_batch(key):
    """A fully-filtered eval batch (B=0) must return empties on the
    kernel path too (it used to ZeroDivisionError in _pad_to)."""
    ky = jax.random.fold_in(key, 1)
    x = jnp.zeros((0, 8))
    y = jax.random.normal(ky, (32, 8))
    t = jnp.zeros((0,), jnp.int32)
    tgt = ops.eval_tgt_scores(x, y, t, interpret=True)
    assert tgt.shape == (0,)
    vals, ids, gt, eq = ops.eval_topk(x, y, tgt, 5, interpret=True)
    assert vals.shape == (0, 5) and ids.shape == (0, 5)
    assert gt.shape == (0,) and eq.shape == (0,)


def test_eval_topk_fewer_valid_columns_than_k(key):
    """k exceeds the valid-column count across multiple tiles: the
    kernel must emit the INT32_MAX placeholder for the exhausted slots
    (not duplicate real ids) — exactly what the reference's lax.top_k
    keeps."""
    b, c, d, k = 6, 6, 8, 5
    kx, ky, kt = jax.random.split(key, 3)
    x = grid_normal(kx, (b, d))
    y = grid_normal(ky, (c, d))
    t = jax.random.randint(kt, (b,), 1, 4)
    # only ids [1, 4) valid → 3 valid columns < k, over 3 tiles of 2
    tgt = ops.eval_tgt_scores(x, y, t, block_c=2, interpret=True)
    got = ops.eval_topk(x, y, tgt, k, block_c=2, c_lo=1, c_hi=4,
                        interpret=True)
    want = ref.eval_topk_ref(
        x, y, ref.eval_tgt_scores_ref(x, y, t, chunk=2),
        k, chunk=2, c_lo=1, c_hi=4,
    )
    for g, w, name in zip(got, want, ["vals", "ids", "gt", "eq"]):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=name
        )
    ids = np.asarray(got[1])
    pad_id = np.iinfo(np.int32).max
    np.testing.assert_array_equal(ids[:, 3:], pad_id)  # exhausted slots
    for row in ids:
        real = row[row != pad_id]
        assert len(set(real.tolist())) == len(real)  # no duplicates


def test_rank_of_target_pessimistic_ties():
    """The bugfix: tied competitors rank ABOVE the target (strict >
    alone hands every tied item the optimistic rank)."""
    scores = jnp.asarray([
        [3.0, 5.0, 5.0, 5.0, 1.0],  # target ties two others
        [9.0, 1.0, 2.0, 3.0, 4.0],  # unique max target
        [2.0, 2.0, 2.0, 2.0, 2.0],  # everything tied
    ])
    targets = jnp.asarray([1, 0, 2])
    ranks = np.asarray(core_metrics.rank_of_target(scores, targets))
    # row 0: none greater, two non-target ties → rank 2 (optimistic: 0)
    # row 1: unique best → 0
    # row 2: four non-target ties → 4
    np.testing.assert_array_equal(ranks, [2, 0, 4])


def test_streaming_rank_topk_impls_agree(key):
    b, c, d, k = 16, 517, 16, 10
    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, d))
    y = jax.random.normal(ky, (c, d))
    t = jax.random.randint(kt, (b,), 1, c)
    a = streaming_rank_topk(x, y, t, k, block_c=128, c_lo=1, impl="ref")
    bk = streaming_rank_topk(
        x, y, t, k, block_c=128, c_lo=1, impl="kernel", interpret=True
    )
    for g, w in zip(a, bk):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_metric_accumulator_matches_oracle_and_folds(key):
    """One-shot accumulator == topk_metrics; multi-batch fold == the
    accumulator over the concatenation (COV folds as a union)."""
    b, c, d = 48, 200, 12
    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, d))
    y = jax.random.normal(ky, (c, d))
    t = jax.random.randint(kt, (b,), 1, c)
    ks = (1, 5, 10)

    scores, _ = _dense_oracle(x, y, t)
    oracle = core_metrics.topk_metrics(scores, np.asarray(t), ks=ks,
                                       catalog=c)

    vals, ids, gt, eq = streaming_rank_topk(
        x, y, t, max(ks), block_c=64, c_lo=1, impl="ref"
    )
    one = MetricAccumulator(ks, c)
    one.update(ranks_from_counts(gt, eq), np.asarray(ids))
    assert one.result() == pytest.approx(oracle, abs=1e-12)

    folded = MetricAccumulator(ks, c)
    ranks = ranks_from_counts(gt, eq)
    for lo, hi in [(0, 16), (16, 37), (37, b)]:
        folded.update(ranks[lo:hi], np.asarray(ids)[lo:hi])
    assert folded.result() == pytest.approx(one.result(), abs=1e-12)


def test_evaluate_streaming_matches_dense_oracle(key):
    """Full harness (leave-one-out protocol included) against
    core.metrics.evaluate_seqrec on a real SASRec model — both impls."""
    from repro.data import Cursor, SeqDataConfig, SequenceDataset
    from repro.models import sasrec

    cfg = sasrec.SeqRecConfig(
        n_items=300, max_len=20, d_model=16, n_layers=1, n_heads=2,
        dropout=0.0,
    )
    params = sasrec.init_params(key, cfg)
    data = SequenceDataset(SeqDataConfig(
        n_items=300, seq_len=20, batch_size=64,
    ))
    eval_batch, _ = data.eval_batch(Cursor(seed=0))
    oracle = core_metrics.evaluate_seqrec(params, cfg, eval_batch)
    # block_c chosen so catalog_loss_size (304) % block_c != 0
    got_ref = evaluate_streaming(params, cfg, eval_batch, impl="ref",
                                 block_c=96)
    assert got_ref == pytest.approx(oracle, abs=1e-12)
    got_kernel = evaluate_streaming(params, cfg, eval_batch,
                                    impl="kernel", interpret=True,
                                    block_c=96)
    assert got_kernel == pytest.approx(oracle, abs=1e-12)


def test_evaluate_streaming_bert4rec_protocol(key):
    """BERT4Rec Cloze eval: [MASK] at the held-out slot; streaming must
    equal the dense scoring of the same masked forward."""
    from repro.data import Cursor, SeqDataConfig, SequenceDataset
    from repro.eval import bert4rec_score_fn
    from repro.models import bert4rec as b4r

    cfg = b4r.make_config(n_items=200, max_len=16, d_model=16,
                          n_layers=1, n_heads=2, dropout=0.0)
    params = b4r.init_params(key, cfg)
    data = SequenceDataset(SeqDataConfig(
        n_items=200, seq_len=16, batch_size=32,
    ))
    eval_batch, _ = data.eval_batch(Cursor(seed=1))
    got = evaluate_streaming(params, cfg, eval_batch, impl="ref",
                             block_c=64)

    # dense reference with the identical protocol
    tokens = np.asarray(eval_batch["tokens"])
    tokens = tokens[(tokens != 0).sum(1) >= 2]
    b, l = tokens.shape
    targets = tokens[np.arange(b), l - 1].copy()
    states, catalog = bert4rec_score_fn(cfg)(params, jnp.asarray(tokens))
    scores = np.array(states @ catalog.T)
    scores[:, 0] = -1e30
    scores[:, cfg.n_items:] = -1e30  # phantom rows incl. [MASK]
    want = core_metrics.topk_metrics(scores, targets,
                                     catalog=cfg.n_items)
    assert got == pytest.approx(want, abs=1e-12)


def test_eval_memory_model():
    """The acceptance inequality: streaming peak is O(B·(K + block)),
    independent of C; dense is O(B·C)."""
    b, k, block = 512, 10, 512
    stream = eval_peak_elements(b, k, block)
    assert stream == b * (block + 2 * k + 2)
    for c in (10_000, 1_000_000):
        assert dense_eval_elements(b, c) == b * c
        assert stream < dense_eval_elements(b, c)
    # C-independence
    assert eval_peak_elements(b, k, block) == stream
