"""Substrate tests: checkpoint atomic/restore/elastic, data determinism +
resume, optimizer golden steps, gradient compression (DESIGN.md §7)."""
import os

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import (
    ClickDataConfig,
    ClickstreamDataset,
    Cursor,
    GraphDataConfig,
    NeighborSampler,
    SeqDataConfig,
    SequenceDataset,
    random_graph,
)
from repro.optim import (
    adafactor,
    adamw,
    clip_by_global_norm,
    compressed_gradient_transform,
    init_error_feedback,
    linear_warmup_cosine,
    sgd_momentum,
)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    tree = {"w": jnp.arange(6.0).reshape(2, 3), "meta": {"step": 7}}
    mgr.save(3, tree)
    step, back = mgr.restore_latest()
    assert step == 3
    np.testing.assert_array_equal(back["w"], np.arange(6.0).reshape(2, 3))
    assert back["meta"]["step"] == 7


def test_checkpoint_keep_n_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    for s in (1, 5, 9):
        mgr.save(s, {"x": jnp.ones(s)})
    assert mgr.all_steps() == [5, 9]
    step, tree = mgr.restore_latest()
    assert step == 9 and tree["x"].shape == (9,)


def test_checkpoint_crash_mid_write_is_invisible(tmp_path):
    """A stray .tmp dir (crash before the atomic rename) is ignored."""
    mgr = CheckpointManager(str(tmp_path), keep_n=3)
    mgr.save(1, {"x": jnp.ones(2)})
    os.makedirs(tmp_path / "step_2.tmp")  # simulated crash artifact
    (tmp_path / "step_2.tmp" / "leaves.npz").write_bytes(b"garbage")
    assert mgr.latest_step() == 1
    _, tree = mgr.restore_latest()
    assert tree["x"].shape == (2,)


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.zeros(4)}, blocking=False)
    mgr.wait()
    assert mgr.all_steps() == [1]


def test_checkpoint_elastic_resharding(tmp_path):
    """Restore with target shardings (the elastic-restart path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.arange(16.0)})
    mesh = make_mesh((1,), ("data",))
    _, tree = mgr.restore_latest(
        shardings={"w": NamedSharding(mesh, P("data"))}
    )
    assert tree["w"].sharding.spec == P("data")
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.arange(16.0))


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------
def test_sequence_determinism_and_resume():
    ds = SequenceDataset(SeqDataConfig(n_items=500, seq_len=16,
                                       batch_size=4))
    c = Cursor(seed=7)
    stream1 = []
    for _ in range(4):
        b, c = ds.next_batch(c)
        stream1.append(b["tokens"])
    # resume from the middle using only (seed, step)
    c2 = Cursor(seed=7, step=2)
    b3, _ = ds.next_batch(c2)
    np.testing.assert_array_equal(stream1[2], b3["tokens"])


def test_sequence_targets_are_shifted():
    ds = SequenceDataset(SeqDataConfig(n_items=500, seq_len=16,
                                       batch_size=4, min_len_frac=1.0))
    b, _ = ds.next_batch(Cursor(seed=1))
    np.testing.assert_array_equal(b["targets"][:, :-1], b["tokens"][:, 1:])
    assert not b["valid"][:, -1].any()


@hypothesis.given(seed=st.integers(0, 10_000))
@hypothesis.settings(max_examples=10, deadline=None)
def test_clickstream_labels_learnable(seed):
    """Teacher-generated labels are reproducible per cursor."""
    ds = ClickstreamDataset(ClickDataConfig(vocab_sizes=(50, 30),
                                            batch_size=16))
    a, _ = ds.next_batch(Cursor(seed=seed))
    b, _ = ds.next_batch(Cursor(seed=seed))
    np.testing.assert_array_equal(a["labels"], b["labels"])
    np.testing.assert_array_equal(a["sparse_ids"], b["sparse_ids"])


def test_neighbor_sampler_shapes_static():
    g = random_graph(GraphDataConfig(n_nodes=300, n_edges=900, d_feat=8))
    samp = NeighborSampler(g["edge_index"], 300)
    shapes = set()
    c = Cursor(seed=3)
    for _ in range(3):
        b, c = samp.sample(c, batch_nodes=8, fanouts=(4, 3))
        shapes.add((b["node_ids"].shape, b["edge_index"].shape))
        # all real edges reference in-range local node ids
        n_real = int(b["n_real_nodes"])
        assert b["edge_index"].max() < n_real
    assert len(shapes) == 1  # fixed shapes → no jit recompiles


# ---------------------------------------------------------------------------
# Optimizers (golden-step vs numpy reference)
# ---------------------------------------------------------------------------
def test_adamw_golden_step():
    init, update = adamw(0.1, b1=0.9, b2=0.999, eps=1e-8)
    p = {"w": jnp.array([1.0, -2.0])}
    g = {"w": jnp.array([0.5, 0.5])}
    state = init(p)
    new_p, state = update(g, state, p)
    # numpy reference (bias-corrected adam, step 1)
    m = 0.1 * 0.5
    v = 0.001 * 0.25
    mh, vh = m / 0.1, v / 0.001
    want = np.array([1.0, -2.0]) - 0.1 * mh / (np.sqrt(vh) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]), want, rtol=1e-6)


def test_sgd_momentum_golden():
    init, update = sgd_momentum(0.5, momentum=0.9)
    p = {"w": jnp.array([0.0])}
    state = init(p)
    for want in [-0.5, -1.45]:  # v1=1, v2=1.9
        p, state = update({"w": jnp.array([1.0])}, state, p)
        np.testing.assert_allclose(float(p["w"][0]), want, rtol=1e-6)


def test_adafactor_factored_state_is_small():
    init, update = adafactor(1e-2)
    p = {"emb": jnp.zeros((4096, 512))}
    state = init(p)
    leaf = state.inner["v"]["emb"]
    assert set(leaf) == {"vr", "vc"}
    assert leaf["vr"].shape == (4096,) and leaf["vc"].shape == (512,)
    g = {"emb": jnp.ones((4096, 512))}
    new_p, _ = update(g, state, p)
    assert bool(jnp.all(jnp.isfinite(new_p["emb"])))


def test_clip_by_global_norm():
    g = {"a": jnp.ones(4) * 3.0}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), 6.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(clipped["a"])), 1.0, rtol=1e-5
    )


def test_schedule_warmup_then_decay():
    fn = linear_warmup_cosine(1.0, warmup_steps=10, total_steps=110)
    assert float(fn(0)) == 0.0
    np.testing.assert_allclose(float(fn(5)), 0.5)
    np.testing.assert_allclose(float(fn(10)), 1.0, rtol=1e-6)
    assert float(fn(110)) < 0.2


# ---------------------------------------------------------------------------
# Gradient compression (error feedback)
# ---------------------------------------------------------------------------
@hypothesis.given(seed=st.integers(0, 1000))
@hypothesis.settings(max_examples=10, deadline=None)
def test_error_feedback_accumulates_to_truth(seed):
    """Σ_t decompressed_t == Σ_t g_t + residual_T (error feedback is
    lossless in the telescoping sum — Karimireddy et al. 2019)."""
    key = jax.random.PRNGKey(seed)
    g = {"w": jax.random.normal(key, (32,))}
    ef = init_error_feedback(g)
    total_sent = jnp.zeros(32)
    total_true = jnp.zeros(32)
    for t in range(5):
        gt = {"w": jax.random.normal(jax.random.fold_in(key, t), (32,))}
        sent, ef = compressed_gradient_transform(gt, ef)
        total_sent += sent["w"]
        total_true += gt["w"]
    np.testing.assert_allclose(
        np.asarray(total_sent + ef.residual["w"]),
        np.asarray(total_true),
        rtol=1e-4, atol=1e-5,
    )


def test_int8_roundtrip_bounded_error():
    from repro.optim import compress_int8, decompress_int8

    x = jnp.linspace(-3, 3, 100)
    q, scale = compress_int8(x)
    back = decompress_int8(q, scale)
    assert q.dtype == jnp.int8
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale) / 2 + 1e-6


def test_compression_wrapped_optimizer_trains():
    """int8 error-feedback compression wrapped around AdamW still
    descends and carries its residual in the optimizer state."""
    from repro.optim import adamw, with_error_feedback_compression

    init, update = with_error_feedback_compression(adamw(0.1))
    p = {"w": jnp.array([2.0, -3.0, 1.0])}
    state = init(p)
    assert "ef" in state.inner and "base" in state.inner
    for _ in range(25):
        g = {"w": 2 * p["w"]}  # d/dw ||w||^2
        p, state = update(g, state, p)
    assert float(jnp.linalg.norm(p["w"])) < 2.0  # moved toward 0
    assert float(jnp.abs(state.inner["ef"]["w"]).sum()) >= 0.0
