"""Kernel guardrail tests (kernels/guard, KERNELS.md §Guard, DESIGN.md §9).

Three layers, each pinned here:

  * preflight — analytic block-config legality + VMEM models: legal
    configs pass through untouched, illegal ones are auto-repaired to a
    FIXED POINT or raise a structured ``KernelPreflightError`` naming
    the violated rule (the hypothesis property test sweeps randomized
    configs and asserts "repaired-legal or structured error, never an
    uncaught Pallas/XLA exception");
  * conformance — the adversarial differential canaries pass for every
    kernel on this backend; fault-injection drills monkeypatch a kernel
    entry point broken and prove dispatch DEGRADES to the exact ref
    path with a loud warning (policy ``warn``) or raises (``strict``),
    while the retrieval server refuses readiness with a distinct
    ``ServerNotReadyError`` until conformance passes again;
  * sentinels — the on-device NaN/Inf/degenerate-LSE counters count
    right, ride the loss aux into the step metrics, and stay silent on
    healthy steps.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import guard, ops, ref
from repro.kernels.guard import conformance as conf
from repro.kernels.guard.preflight import (
    KNOWN_KERNELS,
    PREFLIGHT_RULES,
    KernelPreflightError,
    preflight,
    vmem_budget_bytes,
)


@pytest.fixture(autouse=True)
def _guard_state():
    """Reset the policy override and drop any failing (fault-injected)
    verdicts after each test; healthy memoized verdicts are kept so the
    canaries run once per session, not once per test."""
    guard.set_policy(None)
    yield
    guard.set_policy(None)
    with conf._LOCK:
        for k in [k for k, v in conf._VERDICTS.items() if not v.passed]:
            del conf._VERDICTS[k]


def _broken_kernel(*args, **kwargs):
    raise RuntimeError("injected miscompile")


# ---------------------------------------------------------------------------
# Preflight: unit
# ---------------------------------------------------------------------------
def test_legal_config_untouched():
    pf = preflight(
        "fused_ce", rows=64, cols=1024, d=32, block_rows=64,
        block_cols=256, backend="cpu",
    )
    assert not pf.repairs
    assert pf.blocks == (64, 256)


def test_tpu_mxu_alignment_repair():
    pf = preflight(
        "fused_ce", rows=1000, cols=10000, d=64, block_rows=100,
        block_cols=500, backend="tpu",
    )
    assert pf.blocks == (104, 512)  # round up to (sublane, lane) multiples
    rules = {r.rule for r in pf.repairs}
    assert rules == {"mxu_alignment"}
    assert pf.loud_repairs  # alignment rewrites are loud


def test_block_gt_dim_clamps_silently():
    pf = preflight(
        "fused_ce", rows=6, cols=10, d=8, block_rows=256, block_cols=512,
        backend="cpu",
    )
    assert pf.blocks == (6, 10)
    assert pf.repairs and not pf.loud_repairs  # normalization, not repair


def test_positive_block_repair_is_loud():
    pf = preflight(
        "fused_ce", rows=64, cols=1024, d=8, block_rows=0, block_cols=-4,
        backend="cpu",
    )
    br, bc = pf.blocks
    assert br >= 1 and bc >= 1
    assert {r.rule for r in pf.loud_repairs} == {"positive_block"}


def test_vmem_budget_repair_converges():
    pf = preflight(
        "linear_sce", rows=4096, cols=200_000, d=4096, block_rows=1024,
        block_cols=8192, backend="tpu",
    )
    assert pf.vmem_bytes <= pf.vmem_budget_bytes
    assert any(r.rule == "vmem_budget" for r in pf.repairs)
    # The repair is a fixed point: the repaired config round-trips clean.
    br, bc = pf.blocks
    pf2 = preflight(
        "linear_sce", rows=4096, cols=200_000, d=4096, block_rows=br,
        block_cols=bc, backend="tpu",
    )
    assert not pf2.repairs and tuple(pf2.blocks) == (br, bc)


def test_vmem_budget_unrepairable_raises():
    # d so large that even the minimum (8, 128) tile overflows VMEM.
    with pytest.raises(KernelPreflightError) as ei:
        preflight(
            "fused_ce", rows=8, cols=128, d=65536, block_rows=8,
            block_cols=128, backend="tpu",
        )
    assert ei.value.rule == "vmem_budget"
    assert ei.value.kernel == "fused_ce"


def test_vmem_budget_env_override(monkeypatch):
    base = vmem_budget_bytes()
    monkeypatch.setenv("REPRO_GUARD_VMEM_MB", "64")
    assert vmem_budget_bytes() == 64 * 2**20 != base
    # A config the default 12 MB budget shrinks fits a 64 MB budget.
    pf = preflight(
        "fused_ce", rows=2048, cols=65536, d=512, block_rows=512,
        block_cols=2048, backend="tpu",
    )
    assert not any(r.rule == "vmem_budget" for r in pf.repairs)


def test_structured_rejections():
    with pytest.raises(KernelPreflightError) as ei:
        preflight("warp_drive", rows=8, cols=8, d=8, block_rows=8,
                  block_cols=8)
    assert ei.value.rule == "unknown_kernel"
    with pytest.raises(KernelPreflightError) as ei:
        preflight("fused_ce", rows=8, cols=8, d=8, block_rows=8,
                  block_cols=8, dtype="int8")
    assert ei.value.rule == "dtype_supported"
    for bad in (dict(rows=0), dict(d=-3), dict(k=0)):
        with pytest.raises(KernelPreflightError) as ei:
            preflight("fused_ce", **{**dict(
                rows=8, cols=8, d=8, k=None), **bad},
                block_rows=8, block_cols=8)
        assert ei.value.rule == "positive_dims"


def test_checked_blocks_policy_off_passthrough():
    guard.set_policy("off")
    assert guard.checked_blocks(
        "warp_drive", rows=-1, cols=0, d=0, block_rows=-5, block_cols=0
    ) == (-5, 0)


def test_checked_blocks_empty_batch_passthrough():
    """rows == 0 (a fully-filtered eval batch) is a legal no-op: the
    kernel front-ends return empties without launching anything, so
    checked_blocks must pass the config through rather than let the
    positive_dims rule reject a dispatch that never happens."""
    assert guard.checked_blocks(
        "eval_fused", rows=0, cols=32, d=8, block_rows=128, block_cols=512,
    ) == (128, 512)


def test_checked_blocks_warns_on_loud_repair():
    with pytest.warns(RuntimeWarning, match="auto-repaired"):
        br, bc = guard.checked_blocks(
            "fused_ce", rows=64, cols=256, d=8, block_rows=0,
            block_cols=128,
        )
    assert br >= 1 and bc == 128


@settings(max_examples=50, deadline=None)
@given(
    kernel_i=st.integers(min_value=0, max_value=len(KNOWN_KERNELS)),
    rows=st.integers(min_value=-2, max_value=5000),
    cols=st.integers(min_value=-2, max_value=300_000),
    d=st.integers(min_value=-1, max_value=8192),
    block_rows=st.integers(min_value=-8, max_value=4096),
    block_cols=st.integers(min_value=-8, max_value=16384),
    k_raw=st.integers(min_value=-1, max_value=64),
    dtype_i=st.integers(min_value=0, max_value=2),
    backend_i=st.integers(min_value=0, max_value=1),
)
def test_preflight_property_repair_or_structured_error(
    kernel_i, rows, cols, d, block_rows, block_cols, k_raw, dtype_i,
    backend_i,
):
    """Any config either round-trips to a LEGAL fixed point or raises a
    structured KernelPreflightError naming a known rule — never an
    uncaught exception reaching Pallas/XLA."""
    kernel = (KNOWN_KERNELS + ("not_a_kernel",))[kernel_i]
    dtype = ("float32", "bfloat16", "int8")[dtype_i]
    backend = ("cpu", "tpu")[backend_i]
    k = None if k_raw < 0 else k_raw
    try:
        pf = preflight(
            kernel, rows=rows, cols=cols, d=d, block_rows=block_rows,
            block_cols=block_cols, dtype=dtype, k=k, backend=backend,
        )
    except KernelPreflightError as e:
        assert e.rule in PREFLIGHT_RULES
        assert e.kernel == kernel
        return
    br, bc = pf.blocks
    assert 1 <= br <= rows and 1 <= bc <= cols
    if backend == "tpu":
        assert pf.vmem_bytes <= pf.vmem_budget_bytes
    pf2 = preflight(
        kernel, rows=rows, cols=cols, d=d, block_rows=br, block_cols=bc,
        dtype=dtype, k=k, backend=backend,
    )
    assert not pf2.repairs
    assert tuple(pf2.blocks) == (br, bc)


# ---------------------------------------------------------------------------
# Conformance: canaries pass here; verdicts memoize; JSON snapshot
# ---------------------------------------------------------------------------
def test_all_canaries_pass_on_this_backend():
    verdicts = guard.run_conformance()
    assert set(verdicts) == set(conf.kernels())
    assert len(verdicts) == 8
    for name, v in verdicts.items():
        assert v.passed, f"{name}: {v.failures}"
        assert v.n_pass >= 1 and v.n_fail == 0


def test_verdict_memoized_until_cleared():
    v1 = guard.verdict_for("fused_ce")
    assert guard.verdict_for("fused_ce") is v1
    guard.clear_verdicts("fused_ce")
    v2 = guard.verdict_for("fused_ce")
    assert v2 is not v1 and v2.passed


def test_verdict_table_is_json_ready():
    import json

    guard.verdict_for("fused_ce")
    table = guard.verdict_table()
    assert table and json.dumps(table)
    row = table[0]
    assert {"kernel", "backend", "interpret", "passed", "n_pass",
            "n_fail", "failures"} <= set(row)


def test_unknown_kernel_verdict_raises():
    with pytest.raises(KeyError):
        guard.verdict_for("warp_drive")


def test_healthy_dispatch_is_warning_silent(key):
    x = jax.random.normal(key, (6, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (10, 8))
    tgt = jnp.arange(6, dtype=jnp.int32) % 10
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = ops.fused_ce_loss(x, y, tgt)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.fused_ce_loss_ref(x, y, tgt)),
        atol=1e-5, rtol=1e-5,
    )


# ---------------------------------------------------------------------------
# Fault injection: broken kernel → degrade (warn) / raise (strict) /
# passthrough (off)
# ---------------------------------------------------------------------------
def test_broken_kernel_degrades_to_ref_with_warning(monkeypatch, key):
    import repro.kernels.mips_topk as mips_mod

    monkeypatch.setattr(mips_mod, "mips_topk", _broken_kernel)
    guard.clear_verdicts("mips_topk")
    q = jax.random.normal(key, (6, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (10, 8))
    with pytest.warns(RuntimeWarning, match="DEGRADING"):
        vals, ids = ops.mips_topk(q, y, 4)
    want_v, want_i = ref.mips_topk_ref(q, y, 4, chunk=4)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_v))
    v = guard.verdict_for("mips_topk")
    assert not v.passed
    assert any("injected miscompile" in f for f in v.failures)


def test_broken_kernel_strict_raises(monkeypatch, key):
    import repro.kernels.mips_topk as mips_mod

    monkeypatch.setattr(mips_mod, "mips_topk", _broken_kernel)
    guard.clear_verdicts("mips_topk")
    guard.set_policy("strict")
    q = jax.random.normal(key, (6, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (10, 8))
    with pytest.raises(guard.KernelConformanceError) as ei:
        ops.mips_topk(q, y, 4)
    assert ei.value.kernel == "mips_topk"
    assert ei.value.failures


def test_broken_kernel_on_tpu_raises_under_warn(monkeypatch, key):
    """On TPU a failed canary never degrades to the XLA reference, even
    under the default ``warn`` policy: the device path must run."""
    import repro.kernels.mips_topk as mips_mod

    monkeypatch.setattr(mips_mod, "mips_topk", _broken_kernel)
    monkeypatch.setattr(conf, "_backend_name", lambda: "tpu")
    guard.clear_verdicts("mips_topk")
    assert guard.policy() == "warn"
    q = jax.random.normal(key, (6, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (10, 8))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # and no DEGRADING warning either
        with pytest.raises(guard.KernelConformanceError) as ei:
            ops.mips_topk(q, y, 4)
    assert ei.value.kernel == "mips_topk"
    assert "tpu" in str(ei.value)


def test_policy_off_is_legacy_passthrough(monkeypatch, key):
    import repro.kernels.mips_topk as mips_mod

    monkeypatch.setattr(mips_mod, "mips_topk", _broken_kernel)
    guard.set_policy("off")
    q = jax.random.normal(key, (6, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (10, 8))
    # No preflight, no verdicts: the broken kernel itself is reached.
    with pytest.raises(RuntimeError, match="injected miscompile"):
        ops.mips_topk(q, y, 4)


def test_broken_loss_kernel_degrades_exactly(monkeypatch, key):
    import repro.kernels.linear_sce as lin_mod

    from repro.core.losses import ce_fused_linear

    monkeypatch.setattr(lin_mod, "linear_ce_loss", _broken_kernel)
    guard.clear_verdicts("linear_sce")
    x = jax.random.normal(key, (6, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (13, 8))
    tgt = jnp.arange(6, dtype=jnp.int32) % 13
    with pytest.warns(RuntimeWarning, match="DEGRADING"):
        loss, aux = ce_fused_linear(x, w, tgt)
    want = jnp.mean(ref.linear_ce_loss_ref(x, w, tgt, chunk=13))
    np.testing.assert_allclose(float(loss), float(want), atol=1e-6)
    assert int(aux["sentinels"]["linear_sce_nonfinite"]) == 0


def test_invalid_policy_rejected():
    with pytest.raises(ValueError):
        guard.set_policy("paranoid")


# ---------------------------------------------------------------------------
# Sentinels
# ---------------------------------------------------------------------------
def test_loss_sentinels_counts():
    per_pos = jnp.asarray([1.0, jnp.nan, jnp.inf, 2.0, -jnp.inf])
    s = guard.loss_sentinels("linear_sce", per_pos)
    assert set(s) == {"linear_sce_nonfinite"}
    assert int(s["linear_sce_nonfinite"]) == 3
    lse = jnp.asarray([0.0, -1e30, 3.0])
    s = guard.loss_sentinels("fused_ce", jnp.zeros(3), lse=lse)
    assert int(s["fused_ce_nonfinite"]) == 0
    assert int(s["fused_ce_degenerate_lse"]) == 1


def test_merge_and_describe_sentinels():
    a = {"k_nonfinite": jnp.int32(2)}
    b = {"k_nonfinite": jnp.int32(3), "j_nonfinite": jnp.int32(0)}
    m = guard.merge_sentinels(a, b)
    assert int(m["k_nonfinite"]) == 5
    assert guard.describe_sentinels(m) == "k_nonfinite=5"
    assert guard.describe_sentinels({"x": jnp.int32(0)}) == ""


def test_vocab_loss_threads_sentinels(key):
    from repro.launch import steps as steps_lib

    y = jax.random.normal(jax.random.PRNGKey(1), (20, 8))
    tgt = jnp.arange(4, dtype=jnp.int32) % 20
    kw = dict(loss_name="ce_fused_linear", sce_cfg=None, sce_mode="exact",
              mesh=None)
    x = jax.random.normal(key, (4, 8))
    loss, s = steps_lib._vocab_loss(x, y, tgt, None, key, **kw)
    assert set(s) == {"linear_sce_nonfinite"}
    assert jnp.isfinite(loss) and int(s["linear_sce_nonfinite"]) == 0
    # A NaN hidden state trips the counter and names the kernel.
    x_bad = x.at[0, 0].set(jnp.nan)
    _, s_bad = steps_lib._vocab_loss(x_bad, y, tgt, None, key, **kw)
    assert int(s_bad["linear_sce_nonfinite"]) > 0
    # ce_chunked carries the degenerate-LSE counter off its online LSE.
    _, s_ck = steps_lib._vocab_loss(
        x, y, tgt, None, key, loss_name="ce_chunked", sce_cfg=None,
        sce_mode="exact", mesh=None,
    )
    assert set(s_ck) == {"ce_chunked_nonfinite", "ce_chunked_degenerate_lse"}
    # Policy off: legacy empty aux — no sentinel pytree leaves at all.
    guard.set_policy("off")
    _, s_off = steps_lib._vocab_loss(x, y, tgt, None, key, **kw)
    assert s_off == {}


def test_apply_update_guarded_surfaces_sentinels():
    from repro.launch import steps as steps_lib

    params = {"w": jnp.ones(3)}
    grads = {"w": jnp.full(3, 0.5)}

    def opt_update(g, state, p):
        return jax.tree.map(lambda pp, gg: pp - gg, p, g), state

    sent = {"linear_sce_nonfinite": jnp.int32(2)}
    new_p, _, metrics = steps_lib._apply_update_guarded(
        opt_update, jnp.float32(1.0), grads, params, (), sentinels=sent
    )
    assert int(metrics["sentinels"]["linear_sce_nonfinite"]) == 2
    assert not bool(metrics["skipped"])
    np.testing.assert_allclose(np.asarray(new_p["w"]), 0.5)
    # NaN loss: step skipped, params bit-identical, no sentinels key
    # when the loss didn't thread any.
    new_p, _, metrics = steps_lib._apply_update_guarded(
        opt_update, jnp.float32(jnp.nan), grads, params, ()
    )
    assert bool(metrics["skipped"]) and "sentinels" not in metrics
    np.testing.assert_array_equal(np.asarray(new_p["w"]),
                                  np.asarray(params["w"]))


# ---------------------------------------------------------------------------
# ops dispatch plumbing (satellite: backend probe / interpret override)
# ---------------------------------------------------------------------------
def test_force_interpret_env(monkeypatch):
    monkeypatch.setattr(ops, "_default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    assert ops._interpret_default() is False
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert ops._interpret_default() is True


def test_default_backend_memoized():
    assert ops._default_backend() == jax.default_backend()
    hits0 = ops._default_backend.cache_info().hits
    ops._default_backend()
    assert ops._default_backend.cache_info().hits == hits0 + 1


def test_interpret_for_backend_cases(monkeypatch):
    assert ops._interpret_for_backend("tpu") is False
    assert ops._interpret_for_backend("cpu") is True
    monkeypatch.setattr(ops, "_gpu_interpret_warned", False)
    with pytest.warns(RuntimeWarning, match="Mosaic-GPU"):
        assert ops._interpret_for_backend("gpu") is True
    with warnings.catch_warnings():  # announced once, not per dispatch
        warnings.simplefilter("error")
        assert ops._interpret_for_backend("gpu") is True


def test_streaming_auto_resolution_degrades(monkeypatch, key):
    from repro.eval import streaming

    x = jax.random.normal(key, (5, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (12, 8))
    tgt = (jnp.arange(5, dtype=jnp.int32) % 11) + 1
    want = streaming.streaming_eval_scores(
        x, y, tgt, 4, block_c=4, c_lo=1, impl="ref"
    )
    monkeypatch.setattr(streaming.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(guard, "kernel_enabled",
                        lambda *a, **k: False)
    got = streaming.streaming_eval_scores(
        x, y, tgt, 4, block_c=4, c_lo=1, impl="auto"
    )
    for g, w in zip(got[:5], want[:5]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# Serve readiness gate (fault-injection drill)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_serve_readiness_drill(monkeypatch):
    import repro.kernels.mips_topk as mips_mod
    from repro.launch.serve import (
        RetrievalServer,
        ServerNotReadyError,
        ServerOverloadedError,
    )

    real_kernel = mips_mod.mips_topk
    monkeypatch.setattr(mips_mod, "mips_topk", _broken_kernel)
    guard.clear_verdicts("mips_topk")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        server = RetrievalServer(buckets=(4,), top_k=4, queue_size=8)
    assert any("DEGRADING" in str(w.message) for w in caught)

    r = np.random.default_rng(0)
    hists = r.integers(
        1, server.cfg.n_items, size=(3, server.cfg.max_len)
    ).astype(np.int32)
    try:
        # Not ready: async submits rejected with the DISTINCT error.
        assert server.ready is False
        assert "mips_topk" in server.readiness_error
        with pytest.raises(ServerNotReadyError) as ei:
            server.submit(hists[0])
        assert not isinstance(ei.value, ServerOverloadedError)
        assert server.rejected == 1
        h = server.health()
        assert h["ready"] is False and h["readiness_error"]
        assert any(not v["passed"] for v in h["conformance"])
        # The bulk path still serves EXACTLY via the degraded-to-ref
        # compiled program (graceful degradation, not an outage).
        vals_deg, ids_deg = server.score(hists)
        assert ids_deg.shape == (3, 4)
        # Fix the kernel, re-run conformance, re-admit traffic.
        monkeypatch.setattr(mips_mod, "mips_topk", real_kernel)
        guard.clear_verdicts("mips_topk")
        assert server.refresh_readiness() is True
        assert server.ready and server.readiness_error is None
        res = server.submit(hists[0]).result(timeout=300.0)
        assert res.k == 4 and res.ids.shape == (4,)
    finally:
        server.close()

    # A healthy server (same seed → same params) built with the gate
    # deferred: not ready until refreshed, then serves the SAME answers
    # the degraded server produced (ref path is exact, not approximate).
    healthy = RetrievalServer(
        buckets=(4,), top_k=4, queue_size=8, defer_readiness=True
    )
    try:
        assert healthy.ready is False
        with pytest.raises(ServerNotReadyError):
            healthy.submit(hists[0])
        assert healthy.refresh_readiness() is True
        vals_ok, ids_ok = healthy.score(hists)
        np.testing.assert_array_equal(ids_deg, ids_ok)
        np.testing.assert_array_equal(vals_deg, vals_ok)
    finally:
        healthy.close()
