"""Distributed tests — run in a subprocess with 8 fake host devices
(``--xla_force_host_platform_device_count=8``), since the main pytest
process must keep the real single-device view (DESIGN.md §7).

Covers: distributed SCE (exact + union) value/grad equality vs the
single-device oracle on dp×tp = 2×4 and 4×2 meshes, the stage-2
candidate clip when bucket_size_y > C/m, distributed top-k, the seqrec
serve/retrieval shard_map steps, and a miniature multi-mesh dry-run
(lower + compile of a real train cell on (2,4) and (2,2,2) meshes).

Meshes come from ``repro.launch.mesh.make_mesh`` (Auto axes);
``shard_map``/``set_mesh`` are JAX's own."""
import os
import subprocess
import sys
import textwrap

import pytest

# Every test here spawns an 8-virtual-device subprocess — the slow tier
# (the CI fast job deselects them; the full tier-1 job runs everything).
pytestmark = pytest.mark.slow

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str):
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        mesh24 = make_mesh((2, 4), ("data", "model"))
        mesh42 = make_mesh((4, 2), ("data", "model"))
        mesh222 = make_mesh((2, 2, 2), ("pod", "data", "model"))
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=REPO_SRC)
    res = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


def test_distributed_sce_exact_and_union_match_oracles():
    _run("""
    from repro.core.distributed_sce import sce_loss_sharded, sce_loss_sharded_ref
    from repro.core.sce import SCEConfig
    key = jax.random.PRNGKey(0)
    N, C, d = 128, 256, 32
    x = jax.random.normal(jax.random.PRNGKey(1), (N, d))
    y = jax.random.normal(jax.random.PRNGKey(2), (C, d)) * 0.5
    t = jax.random.randint(jax.random.PRNGKey(3), (N,), 0, C)
    for cfg in [SCEConfig(8, 16, 32, use_mix=True),
                SCEConfig(8, 16, 32, use_mix=False),
                SCEConfig(8, 16, 32, use_mix=True, use_kernel=True),
                SCEConfig(8, 16, 32, use_mix=True, logit_softcap=10.0)]:
        for mode in ("exact", "union"):
            def f_d(x, y):
                return sce_loss_sharded(x, y, t, key=key, cfg=cfg,
                                        mesh=mesh24, mode=mode)
            def f_r(x, y):
                return sce_loss_sharded_ref(x, y, t, key=key, cfg=cfg,
                                            dp_size=2, mode=mode, tp_size=4)
            with jax.set_mesh(mesh24):
                l = jax.jit(f_d)(x, y)
                g = jax.jit(jax.grad(f_d, argnums=(0, 1)))(x, y)
            lr = f_r(x, y)
            gr = jax.grad(f_r, argnums=(0, 1))(x, y)
            np.testing.assert_allclose(l, lr, rtol=1e-5)
            np.testing.assert_allclose(g[0], gr[0], rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(g[1], gr[1], rtol=1e-4, atol=1e-6)
    print("sce modes ok")
    """)


def test_distributed_sce_dp4_tp2_mesh():
    """Same equality on the transposed (dp=4, tp=2) mesh — both mesh
    aspect ratios from the acceptance grid, gradients finite through
    both modes."""
    _run("""
    from repro.core.distributed_sce import sce_loss_sharded, sce_loss_sharded_ref
    from repro.core.sce import SCEConfig
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
    y = jax.random.normal(jax.random.PRNGKey(2), (256, 32)) * 0.5
    t = jax.random.randint(jax.random.PRNGKey(3), (128,), 0, 256)
    for cfg in [SCEConfig(8, 16, 32, use_mix=True),
                SCEConfig(8, 16, 32, use_mix=True, use_kernel=True)]:
        for mode in ("exact", "union"):
            def f_d(x, y):
                return sce_loss_sharded(x, y, t, key=key, cfg=cfg,
                                        mesh=mesh42, mode=mode)
            def f_r(x, y):
                return sce_loss_sharded_ref(x, y, t, key=key, cfg=cfg,
                                            dp_size=4, mode=mode, tp_size=2)
            with jax.set_mesh(mesh42):
                l = jax.jit(f_d)(x, y)
                g = jax.jit(jax.grad(f_d, argnums=(0, 1)))(x, y)
            np.testing.assert_allclose(l, f_r(x, y), rtol=1e-5)
            gr = jax.grad(f_r, argnums=(0, 1))(x, y)
            np.testing.assert_allclose(g[0], gr[0], rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(g[1], gr[1], rtol=1e-4, atol=1e-6)
            assert np.all(np.isfinite(np.asarray(g[0])))
            assert np.all(np.isfinite(np.asarray(g[1])))
    print("dp4 tp2 ok")
    """)


def test_distributed_sce_bucket_larger_than_catalog_slice():
    """Regression for the exact-mode candidate clip: with
    bucket_size_y > C/m, stage 1 must clip per catalog SLICE and stage 2
    per full catalog, matching the oracle's min(b_y, C) clip."""
    _run("""
    from repro.core.distributed_sce import sce_loss_sharded, sce_loss_sharded_ref
    from repro.core.sce import SCEConfig
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
    y = jax.random.normal(jax.random.PRNGKey(2), (256, 32)) * 0.5
    t = jax.random.randint(jax.random.PRNGKey(3), (128,), 0, 256)
    # C/m = 64 on mesh24 — both a mid case (128) and the full catalog (384>C)
    for b_y in (128, 384):
        cfg = SCEConfig(8, 16, b_y, use_mix=True)
        for mode in ("exact", "union"):
            with jax.set_mesh(mesh24):
                l = jax.jit(lambda x, y: sce_loss_sharded(
                    x, y, t, key=key, cfg=cfg, mesh=mesh24, mode=mode))(x, y)
            lr = sce_loss_sharded_ref(x, y, t, key=key, cfg=cfg,
                                      dp_size=2, mode=mode, tp_size=4)
            np.testing.assert_allclose(l, lr, rtol=1e-5)
    print("clip ok")
    """)


def test_distributed_sce_multipod_mesh():
    _run("""
    from repro.core.distributed_sce import sce_loss_sharded, sce_loss_sharded_ref
    from repro.core.sce import SCEConfig
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
    y = jax.random.normal(jax.random.PRNGKey(2), (256, 32)) * 0.5
    t = jax.random.randint(jax.random.PRNGKey(3), (128,), 0, 256)
    cfg = SCEConfig(8, 16, 32, use_mix=True)
    with jax.set_mesh(mesh222):
        l = jax.jit(lambda x, y: sce_loss_sharded(
            x, y, t, key=key, cfg=cfg, mesh=mesh222))(x, y)
    # pod×data = 4 data shards on the multi-pod mesh
    lr = sce_loss_sharded_ref(x, y, t, key=key, cfg=cfg, dp_size=4)
    np.testing.assert_allclose(l, lr, rtol=1e-5)
    print("multipod ok")
    """)


def test_distributed_topk_exact():
    _run("""
    from repro.dist.collectives import distributed_topk
    scores = jax.random.normal(jax.random.PRNGKey(0), (5, 64))
    def inner(s):
        vals, idx, src = distributed_topk(s, 7, "model")
        return vals, idx, src
    fn = jax.shard_map(inner, mesh=mesh24,
                   in_specs=P(None, "model"),
                   out_specs=(P(None), P(None), P(None)))
    with jax.set_mesh(mesh24):
        vals, idx, src = fn(scores)
    want_vals, want_idx = jax.lax.top_k(scores, 7)
    np.testing.assert_allclose(np.asarray(vals)[:, :7], want_vals, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx)[:, :7], want_idx)
    np.testing.assert_array_equal(np.asarray(src), np.asarray(idx) // 16)
    # single-device fallback outside shard_map: plain top_k
    fv, fi, fs = distributed_topk(scores, 7, "model")
    np.testing.assert_allclose(np.asarray(fv), want_vals, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(fi), want_idx)
    assert int(np.asarray(fs).max()) == 0
    print("topk ok")
    """)


def test_seqrec_serve_and_retrieval_match_dense():
    _run("""
    from repro.configs import get_arch
    from repro.launch import steps as steps_lib
    from repro.models import sasrec
    import dataclasses
    arch = get_arch("sasrec-sce")
    cfg = dataclasses.replace(arch.make_smoke_config(), n_items=512)
    params = sasrec.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, cfg.max_len),
                                1, cfg.n_items)
    serve = steps_lib.make_seqrec_serve_step(arch, cfg, mesh24, top_k=10)
    with jax.set_mesh(mesh24):
        vals, ids = jax.jit(serve)(params, tokens)
    # dense reference
    hidden = sasrec.forward(params, cfg, tokens)
    scores = hidden[:, -1] @ sasrec.item_embeddings(params, cfg).T
    want_vals, want_ids = jax.lax.top_k(scores, 10)
    np.testing.assert_allclose(np.asarray(vals), want_vals, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)

    retr = steps_lib.make_seqrec_retrieval_step(arch, cfg, mesh24, top_k=10)
    cands = jnp.arange(1, 400)
    with jax.set_mesh(mesh24):
        rv, ri = jax.jit(retr)(params, tokens[:1], cands)
    sc = hidden[:1, -1] @ sasrec.item_embeddings(params, cfg)[cands].T  # noqa
    wv, wi = jax.lax.top_k(sc, 10)
    np.testing.assert_allclose(np.asarray(rv), wv, rtol=1e-4)
    print("serve ok")
    """)


def test_mips_serve_differential_restored_ckpt_all_meshes():
    """ISSUE 7 differential: the MIPS-backed serve path on RESTORED
    checkpoint params — single-device, dp×tp 2×4 and 4×2, plus the full
    ``RetrievalServer`` on a mesh — is bit-identical (ids, tie order;
    catalog rows are duplicated so exact ties exist) to the dense
    masked ``lax.top_k`` oracle and to ``eval/streaming``'s fused
    scorer at the same ``[1, n_items)`` window."""
    _run("""
    import dataclasses, tempfile
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_arch
    from repro.dist.sharding import seqrec_serve_shardings
    from repro.eval.streaming import streaming_eval_scores
    from repro.launch import steps as steps_lib
    from repro.launch.serve import RetrievalServer
    from repro.models import sasrec

    arch = get_arch("sasrec-sce")
    cfg = arch.make_smoke_config()  # the config RetrievalServer serves
    params = sasrec.init_params(jax.random.PRNGKey(0), cfg)
    half = cfg.n_items // 2
    params["item_emb"] = params["item_emb"].at[half:cfg.n_items].set(
        params["item_emb"][:half])  # engineered exact score ties
    tmp = tempfile.mkdtemp()
    CheckpointManager(tmp).save(
        5, {"params": params, "opt_state": {}, "step": np.asarray(5)})
    mgr = CheckpointManager(tmp)
    step_h, params_h = mgr.restore_params_latest()
    assert step_h == 5

    k = 10
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, cfg.max_len),
                                1, cfg.n_items)
    # dense masked oracle on the restored params
    hidden = sasrec.forward(params_h, cfg, tokens)
    y = sasrec.loss_catalog(params_h, cfg)
    scores = hidden[:, -1] @ y.T
    gid = jnp.arange(y.shape[0])
    scores = jnp.where((gid[None, :] >= 1) & (gid[None, :] < cfg.n_items),
                       scores, -1e30)
    want_vals, want_ids = jax.lax.top_k(scores, k)
    want_ids = np.asarray(want_ids); want_vals = np.asarray(want_vals)
    assert ((want_ids >= half) & (want_ids < cfg.n_items)).any(), \\
        "tie construction failed to reach the top-k"

    # eval/streaming's fused scorer at the same window
    sv, si = streaming_eval_scores(
        hidden[:, -1], y, jnp.ones((8,), jnp.int32), k,
        c_lo=1, c_hi=cfg.n_items)[:2]
    np.testing.assert_array_equal(np.asarray(si), want_ids)
    np.testing.assert_allclose(np.asarray(sv), want_vals, rtol=1e-6)

    # single-device MIPS serve step
    v0, i0 = jax.jit(steps_lib.make_seqrec_mips_serve_step(
        arch, cfg, None, top_k=k))(params_h, tokens)
    np.testing.assert_array_equal(np.asarray(i0), want_ids)
    np.testing.assert_allclose(np.asarray(v0), want_vals, rtol=1e-6)

    # sharded: restore WITH serve shardings onto each mesh, then serve
    for mesh in (mesh24, mesh42):
        _, params_m = mgr.restore_params_latest(
            shardings=seqrec_serve_shardings(cfg, mesh))
        serve = steps_lib.make_seqrec_mips_serve_step(
            arch, cfg, mesh, top_k=k)
        with jax.set_mesh(mesh):
            v, i = jax.jit(serve)(params_m, tokens)
        np.testing.assert_array_equal(np.asarray(i), want_ids)
        np.testing.assert_allclose(np.asarray(v), want_vals, rtol=1e-6)

    # the full server on mesh24: checkpoint restore + bucket routing
    server = RetrievalServer(
        "sasrec-sce", buckets=(4, 8), top_k=k, mesh=mesh24, ckpt_dir=tmp)
    assert server.restored_step == 5
    vals, ids = server.score(np.asarray(tokens, np.int32)[:6])
    np.testing.assert_array_equal(ids, want_ids[:6])
    np.testing.assert_allclose(vals, want_vals[:6], rtol=1e-6)
    assert server.cache_misses == 0
    server.close()
    print("mips serve differential ok")
    """)


def test_mini_dryrun_lower_compile_both_meshes():
    """A REAL train cell (reduced widths via smoke config machinery is not
    enough — use bert4rec full config with the small batch shape) must
    lower AND compile on single-pod and multi-pod minis; the dist
    collectives must self-report their exact-mode all_to_all payloads."""
    _run("""
    from repro.configs import get_arch
    from repro.configs.common import ShapeSpec
    from repro.dist import collectives as coll_lib
    from repro.launch.cells import _seqrec_cell
    arch = get_arch("bert4rec")
    shape = ShapeSpec("train_batch", "train", {"batch": 32})
    for mesh in (mesh24, mesh222):
        cell = _seqrec_cell(arch, shape, mesh)
        coll_lib.reset_payload_log()
        compiled = cell.lower().compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes >= 0
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else (cost or {})
        assert cost.get("flops", 1) > 0
        modeled = coll_lib.payload_summary()
        # ids-only exact-mode SCE ships (value, global-id) candidate
        # pairs via the distributed_topk_from_local all-gathers;
        # embedding rows never cross the wire (no all_to_all anymore).
        assert modeled["counts"].get("all-gather", 0) >= 2, modeled
        assert modeled["counts"].get("all-to-all", 0) == 0, modeled
        assert modeled["total_bytes"] > 0
    print("mini dryrun ok")
    """)


def test_streaming_eval_sharded_matches_oracle():
    """repro.eval on the dp×tp = 4×2 (and 2×4) meshes: catalog sharded
    over ``model``, batch over ``data``, rank counts psum'd, top-k
    merged through distributed_topk_from_local — must equal the dense
    single-device ``core.metrics`` oracle exactly, including a
    tie-heavy integer-embedding case and C_local % chunk != 0 tails."""
    _run("""
    from repro.core import metrics as core_metrics
    from repro.core.metrics import evaluate_seqrec
    from repro.data import Cursor, SeqDataConfig, SequenceDataset
    from repro.eval import evaluate_streaming, ranks_from_counts
    from repro.eval.harness import _evaluate_sharded  # noqa
    from repro.models import sasrec

    # --- full harness on a real model ---------------------------------
    cfg = sasrec.SeqRecConfig(n_items=300, max_len=20, d_model=16,
                              n_layers=1, n_heads=2, dropout=0.0)
    params = sasrec.init_params(jax.random.PRNGKey(0), cfg)
    data = SequenceDataset(SeqDataConfig(n_items=300, seq_len=20,
                                         batch_size=64))
    eb, _ = data.eval_batch(Cursor(seed=0))
    oracle = evaluate_seqrec(params, cfg, eb)
    # catalog_loss_size = 304 → C_local = 152 on tp=2; 152 % 64 != 0
    for mesh in (mesh42, mesh24):
        got = evaluate_streaming(params, cfg, eb, mesh=mesh, block_c=64)
        for key_ in oracle:
            assert abs(got[key_] - oracle[key_]) < 1e-12, (key_, got)
    print("sharded harness ok")

    # --- tie-heavy integer case at the shard_map scorer level ---------
    from repro.dist.collectives import distributed_topk_from_local
    from repro.dist.sharding import batch_spec, catalog_spec
    from repro.kernels import ops
    b, c, d, k = 16, 96, 8, 10
    ks_ = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.randint(ks_[0], (b, d), -3, 4).astype(jnp.float32)
    y = jax.random.randint(ks_[1], (c, d), -2, 3).astype(jnp.float32)
    y = y.at[c // 2:].set(y[: c - c // 2])  # exact duplicate rows
    t = jax.random.randint(ks_[2], (b,), 1, c)

    def inner(x_l, y_l, t_l):
        c_local = y_l.shape[0]
        off = jax.lax.axis_index("model") * c_local
        tgt = jax.lax.psum(
            ops.eval_tgt_scores(x_l, y_l, t_l, block_c=20, id_offset=off),
            "model")
        vals_l, ids_l, gt_l, eq_l = ops.eval_topk(
            x_l, y_l, tgt, k, block_c=20, c_lo=1, c_hi=c, id_offset=off)
        gt = jax.lax.psum(gt_l, "model")
        eq = jax.lax.psum(eq_l, "model")
        vals, gids = distributed_topk_from_local(vals_l, ids_l, k, "model")
        return vals, gids, gt, eq

    fn = jax.shard_map(inner, mesh=mesh42,
                   in_specs=(batch_spec(mesh42, 2), catalog_spec(mesh42),
                             batch_spec(mesh42, 1)),
                   out_specs=(batch_spec(mesh42, 2), batch_spec(mesh42, 2),
                              batch_spec(mesh42, 1), batch_spec(mesh42, 1)))
    with jax.set_mesh(mesh42):
        vals, gids, gt, eq = jax.jit(fn)(x, y, t)
    scores = np.array(x @ y.T)
    scores[:, 0] = -1e30
    dv, di = jax.lax.top_k(jnp.asarray(scores), k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(dv))
    np.testing.assert_array_equal(np.asarray(gids), np.asarray(di))
    want_ranks = np.asarray(core_metrics.rank_of_target(
        jnp.asarray(scores), t))
    np.testing.assert_array_equal(ranks_from_counts(gt, eq), want_ranks)
    assert (np.asarray(eq) > 1).any()  # ties actually present
    print("sharded ties ok")
    """)


def test_streaming_lm_eval_sharded_matches_single_device():
    """ISSUE 4/5 acceptance: the LM token-rank protocol on dp×tp = 2×4
    AND 4×2 meshes — vocab table sharded over ``model`` (the same
    vocab-parallel layout the SCE loss uses, phantom padded rows
    masked by ``c_hi``), the ``B·T`` position rows over ``data`` —
    must equal the single-device streaming result exactly (which
    test_lm_eval.py pins against the dense (B·T, V) oracle) on every
    rank metric. The next-token ``loss`` now also comes from the
    sharded fused sweep (per-shard online-LSE carries merged via the
    shifted-sum psum/pmax combine — the replicated ``ce_chunked``
    V-sweep is gone), so it matches the single-device fold to f32
    rounding rather than bit-for-bit."""
    _run("""
    from repro.data import Cursor, SeqDataConfig, SequenceDataset
    from repro.eval import evaluate_streaming_lm
    from repro.models import transformer as tf_lib

    # vocab 120 → vocab_padded 128: phantom rows on every shard; B·T =
    # 6·10 = 60 rows pads to dp (2 and 4) by last-row repetition
    cfg = tf_lib.TransformerConfig(
        vocab=120, n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=64, remat=False)
    params = tf_lib.init_params(jax.random.PRNGKey(0), cfg)
    ds = SequenceDataset(SeqDataConfig(
        n_items=cfg.vocab, seq_len=10, batch_size=6, min_len_frac=0.5))
    eb, _ = ds.heldout_batch(Cursor(seed=0))
    # 64 vocab rows per shard on tp=2, 32 on tp=4; block_c=24 leaves a
    # C_local % block != 0 tail on both
    want = evaluate_streaming_lm(params, cfg, eb, impl="ref", block_c=24)
    assert want["n_tokens"] > 0
    for mesh in (mesh24, mesh42):
        got = evaluate_streaming_lm(params, cfg, eb, mesh=mesh,
                                    block_c=24)
        for key_ in want:
            tol = 1e-6 if key_ == "loss" else 0.0
            assert abs(got[key_] - want[key_]) <= tol, (
                dict(mesh.shape), key_, got, want)
    print("sharded lm eval ok")
    """)


def test_fused_eval_sharded_scorer_with_lse_merge():
    """ISSUE 5 scorer-level acceptance: the fused sharded dataflow —
    psum'd ``eval_tgt_gather`` pre-stage, ONE per-shard fused sweep,
    psum'd rank counts, ``distributed_topk_from_local`` candidate
    merge, ``distributed_lse_from_local`` shifted-sum LSE merge — on a
    tie-heavy integer case with C_local % block != 0 tails: ranks, ids
    and target scores equal the dense single-device oracle EXACTLY;
    the merged logsumexp matches dense to f32 rounding."""
    _run("""
    from repro.core import metrics as core_metrics
    from repro.dist.collectives import (
        distributed_lse_from_local, distributed_topk_from_local)
    from repro.dist.sharding import batch_spec, catalog_spec
    from repro.eval import ranks_from_counts
    from repro.kernels import ops
    b, c, d, k = 16, 96, 8, 10
    ks_ = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.randint(ks_[0], (b, d), -3, 4).astype(jnp.float32)
    y = jax.random.randint(ks_[1], (c, d), -2, 3).astype(jnp.float32)
    y = y.at[c // 2:].set(y[: c - c // 2])  # exact duplicate rows
    t = jax.random.randint(ks_[2], (b,), 1, c)

    def inner(x_l, y_l, t_l):
        c_local = y_l.shape[0]
        off = jax.lax.axis_index("model") * c_local
        tgt = jax.lax.psum(
            ops.eval_tgt_gather(x_l, y_l, t_l, block_c=20, id_offset=off),
            "model")
        vals_l, ids_l, gt_l, eq_l, _t, m_l, s_l = ops.eval_fused(
            x_l, y_l, t_l, k, tgt_scores=tgt, block_c=20,
            c_lo=1, c_hi=c, id_offset=off, with_lse=True)
        gt = jax.lax.psum(gt_l, "model")
        eq = jax.lax.psum(eq_l, "model")
        vals, gids = distributed_topk_from_local(vals_l, ids_l, k, "model")
        lse = distributed_lse_from_local(m_l, s_l, "model")
        return vals, gids, gt, eq, tgt, lse

    fn = jax.shard_map(inner, mesh=mesh42,
                   in_specs=(batch_spec(mesh42, 2), catalog_spec(mesh42),
                             batch_spec(mesh42, 1)),
                   out_specs=(batch_spec(mesh42, 2), batch_spec(mesh42, 2))
                   + (batch_spec(mesh42, 1),) * 4)
    with jax.set_mesh(mesh42):
        vals, gids, gt, eq, tgt, lse = jax.jit(fn)(x, y, t)
    scores = np.array(x @ y.T)
    want_tgt = scores[np.arange(b), np.asarray(t)]
    scores[:, 0] = -1e30
    dv, di = jax.lax.top_k(jnp.asarray(scores), k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(dv))
    np.testing.assert_array_equal(np.asarray(gids), np.asarray(di))
    want_ranks = np.asarray(core_metrics.rank_of_target(
        jnp.asarray(scores), t))
    np.testing.assert_array_equal(ranks_from_counts(gt, eq), want_ranks)
    assert (np.asarray(eq) > 1).any()  # ties actually present
    # integer-exact embeddings: the gather matmul target is exact too
    np.testing.assert_array_equal(np.asarray(tgt), want_tgt)
    want_lse = np.asarray(jax.nn.logsumexp(jnp.asarray(scores), axis=-1))
    np.testing.assert_allclose(np.asarray(lse), want_lse,
                               rtol=1e-6, atol=1e-6)
    print("fused sharded scorer ok")
    """)


def test_sharded_mips_topk_stage1_matches_dense():
    """Per-shard stage-1 candidate selection through ops.mips_topk (the
    interpret/shard_map fallback routes to the chunked reference on
    CPU), merged via distributed_topk_from_local — must reproduce the
    dense full-catalog lax.top_k exactly, ids and tie order included."""
    _run("""
    from repro.dist.collectives import distributed_topk_from_local
    from repro.dist.sharding import catalog_spec, replicated_spec
    from repro.kernels import ops
    n_b, c, d, k = 6, 96, 8, 20
    ks_ = jax.random.split(jax.random.PRNGKey(5), 2)
    b = jax.random.randint(ks_[0], (n_b, d), -3, 4).astype(jnp.float32)
    y = jax.random.randint(ks_[1], (c, d), -2, 3).astype(jnp.float32)
    y = y.at[c // 2:].set(y[: c - c // 2])  # tie-heavy duplicates

    def inner(y_l):
        c_local = y_l.shape[0]
        off = jax.lax.axis_index("model") * c_local
        vals_l, gids_l = ops.mips_topk(
            b, y_l, min(k, c_local), block_c=20, id_offset=off)
        return distributed_topk_from_local(vals_l, gids_l, k, "model")

    fn = jax.shard_map(inner, mesh=mesh24, in_specs=catalog_spec(mesh24),
                   out_specs=(replicated_spec(), replicated_spec()))
    with jax.set_mesh(mesh24):
        vals, gids = jax.jit(fn)(y)
    wv, wi = jax.lax.top_k(b @ y.T, k)
    np.testing.assert_array_equal(np.asarray(gids), np.asarray(wi))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(wv), rtol=1e-6)
    print("sharded mips ok")
    """)


def test_all_to_all_bucket_shuffle_routing():
    """Direct coverage for the bucket-routing primitive (its former
    implicit coverage via exact-mode SCE ended with the ids-only
    rewrite): shard j must end up holding every shard's payload for the
    buckets it owns, and the single-device fallback must reshape to the
    m=1 layout."""
    _run("""
    from repro.dist.collectives import all_to_all_bucket_shuffle
    n_b, m = 8, 4  # mesh24 model axis
    def inner(x_l):
        # per-shard payload: value encodes (source shard, bucket)
        src = jax.lax.axis_index("model")
        payload = x_l + 100 * src
        return all_to_all_bucket_shuffle(payload, "model")
    base = jnp.arange(n_b, dtype=jnp.float32)
    fn = jax.shard_map(inner, mesh=mesh24,
                   in_specs=P(), out_specs=P(None, "model"))
    with jax.set_mesh(mesh24):
        out = jax.jit(fn)(base)  # (m, n_b/m * m) over shards
    out = np.asarray(out).reshape(m, m, n_b // m)
    for owner in range(m):
        for src in range(m):
            want = 100 * src + np.arange(
                owner * (n_b // m), (owner + 1) * (n_b // m))
            np.testing.assert_array_equal(out[src, owner], want)
    # single-device fallback: reshape to the m=1 collective layout
    solo = all_to_all_bucket_shuffle(base, "model")
    assert solo.shape == (1, n_b)
    np.testing.assert_array_equal(np.asarray(solo)[0], np.asarray(base))
    print("shuffle ok")
    """)


def test_collective_bytes_parser():
    """The HLO collective parser must count the collectives a known
    program produces."""
    _run("""
    from repro.launch.dryrun import collective_bytes
    def f(x):
        return jax.lax.psum(x, "model")
    fn = jax.shard_map(f, mesh=mesh24, in_specs=P("model"), out_specs=P())
    with jax.set_mesh(mesh24):
        lowered = jax.jit(fn).lower(jnp.ones((64,)))
    hlo = lowered.compile().as_text()
    out = collective_bytes(hlo, 8)
    assert out["counts"]["all-reduce"] >= 1, out
    assert out["total_bytes"] > 0
    print("parser ok", out["counts"])
    """)
