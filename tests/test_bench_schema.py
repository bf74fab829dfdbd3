"""Schema smoke tests for the CI benchmark artifacts (ISSUE 4/5/7
satellites): run the ``--json`` bench CLIs at smoke scale and assert
the required keys/types of ``BENCH_metric_memory.json`` /
``BENCH_sce_pipeline.json`` / ``BENCH_eval_pipeline.json`` /
``BENCH_lm_loss.json`` / ``BENCH_serve.json`` / ``BENCH_ckpt.json`` — so
benchmark refactors can't silently break the perf-trajectory tracking
the CI artifacts accumulate."""
import json
import numbers
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.join(os.path.dirname(__file__), "..")


def _run_bench(tmp_path, module, *args):
    out = tmp_path / "bench.json"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run(
        [sys.executable, "-m", module, *args, "--json", str(out)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600,
    )
    assert res.returncode == 0, (
        f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    )
    with open(out) as f:
        return json.load(f)


def _assert_row(row, spec, ctx):
    """spec: {key: type-or-tuple}; None values allowed only where the
    spec lists NoneType in the tuple."""
    for name, types in spec.items():
        assert name in row, f"{ctx}: missing key {name!r} in {row}"
        assert isinstance(row[name], types), (
            f"{ctx}: {name!r} has type {type(row[name]).__name__}, "
            f"wanted {types}: {row[name]!r}"
        )


def test_metric_memory_json_schema(tmp_path):
    """BENCH_metric_memory.json: the loss-comparison rows CI uploads —
    every paper-loss row present, metric/memory/time columns typed."""
    doc = _run_bench(
        tmp_path, "benchmarks.metric_memory", "--steps", "1"
    )
    assert set(doc) == {"steps", "rows", "derived"}
    assert doc["steps"] == 1
    assert isinstance(doc["derived"], str) and "sce_vs_ce" in doc["derived"]
    rows = doc["rows"]
    assert {r["loss"] for r in rows} >= {
        "ce", "bce_plus", "gbce", "ce_minus", "ce_inbatch", "ce_pop",
        "rece", "sce",
    }
    spec = {
        "loss": str,
        "ndcg@10": numbers.Real,
        "hr@10": numbers.Real,
        "cov@10": numbers.Real,
        "mem_elems": numbers.Integral,
        "eval_mem_elems": numbers.Integral,
        "eval_dense_elems": numbers.Integral,
        "time_s": numbers.Real,
    }
    for row in rows:
        _assert_row(row, spec, f"metric_memory[{row.get('loss')}]")
        assert 0 < row["eval_mem_elems"] < row["eval_dense_elems"]


def test_sce_pipeline_json_schema(tmp_path):
    """BENCH_sce_pipeline.json: the staged dense-vs-fused rows — all
    four stages present; the gather stage's timings are the documented
    nulls (analytic elements only), every other stage fully timed."""
    doc = _run_bench(
        tmp_path, "benchmarks.kernel_bench",
        "--mode", "sce-pipeline", "--catalog", "512", "--positions", "128",
    )
    assert set(doc) == {"mode", "rows", "derived"}
    assert doc["mode"] == "sce-pipeline"
    assert isinstance(doc["derived"], str)
    rows = {r["stage"]: r for r in doc["rows"]}
    assert set(rows) == {"selection", "gather", "loss", "total"}
    spec = {
        "shape": str,
        "stage": str,
        "dense_peak_elems": numbers.Integral,
        "fused_peak_elems": numbers.Integral,
    }
    for stage, row in rows.items():
        _assert_row(row, spec, f"sce_pipeline[{stage}]")
        timed = (numbers.Real,) if stage != "gather" else (type(None),)
        assert isinstance(row["dense_us"], timed), stage
        assert isinstance(row["fused_interp_us"], timed), stage
    assert (
        rows["total"]["fused_peak_elems"]
        < rows["total"]["dense_peak_elems"]
    )


def test_eval_pipeline_json_schema(tmp_path):
    """BENCH_eval_pipeline.json: the two-pass vs fused eval scorer rows
    — both protocols and both paths present with timed stages; the
    ``total`` rows carry the analytic catalog-matmul FLOP / HBM /
    peak-element columns; the fused/two-pass FLOP ratio meets the
    ISSUE 5 acceptance (≤ 0.55 seqrec, ≤ 0.40 LM) and fused peak
    memory is no worse than the two-pass ``B·(block+2K+2)`` model."""
    doc = _run_bench(
        tmp_path, "benchmarks.kernel_bench",
        "--mode", "eval-pipeline",
        "--catalog", "1024", "--positions", "128", "--block-c", "64",
    )
    assert set(doc) == {"mode", "rows", "derived"}
    assert doc["mode"] == "eval-pipeline"
    assert isinstance(doc["derived"], str)
    rows = {
        (r["protocol"], r["path"], r["stage"]): r for r in doc["rows"]
    }
    assert set(rows) == {
        ("seqrec", "two-pass", "tgt"), ("seqrec", "two-pass", "rank"),
        ("seqrec", "two-pass", "total"),
        ("seqrec", "fused", "tgt-gather"), ("seqrec", "fused", "sweep"),
        ("seqrec", "fused", "total"),
        ("lm", "two-pass", "tgt"), ("lm", "two-pass", "rank"),
        ("lm", "two-pass", "nll"), ("lm", "two-pass", "total"),
        ("lm", "fused", "tgt-gather"), ("lm", "fused", "sweep"),
        ("lm", "fused", "total"),
    }
    for key_, row in rows.items():
        _assert_row(row, {"wall_us": numbers.Real}, f"eval_pipeline{key_}")
        if key_[2] == "total":
            _assert_row(row, {
                "matmul_flops": numbers.Integral,
                "hbm_bytes": numbers.Integral,
                "peak_elems": numbers.Integral,
            }, f"eval_pipeline{key_}")
    for protocol, bound in (("seqrec", 0.55), ("lm", 0.40)):
        fused = rows[(protocol, "fused", "total")]
        twopass = rows[(protocol, "two-pass", "total")]
        ratio = fused["flop_ratio_vs_twopass"]
        assert ratio == pytest.approx(
            fused["matmul_flops"] / twopass["matmul_flops"]
        )
        assert ratio <= bound, (protocol, ratio)
        assert fused["hbm_bytes"] < twopass["hbm_bytes"], protocol
        assert fused["peak_elems"] <= twopass["peak_elems"], protocol


def test_serve_json_schema(tmp_path):
    """BENCH_serve.json: per-bucket serving latency rows through the
    real async queue + AOT bucket programs (ISSUE 7) — p50/p99/QPS
    typed and ordered sanely, and the ``recompiles`` column (the
    server's jit cache-miss counter) pinned to ZERO across the whole
    bucket set: the bucket router never escapes the static shape set."""
    doc = _run_bench(
        tmp_path, "benchmarks.kernel_bench",
        "--mode", "serve", "--serve-buckets", "4,8",
        "--serve-requests", "16",
    )
    assert set(doc) == {"mode", "rows", "derived"}
    assert doc["mode"] == "serve"
    assert isinstance(doc["derived"], str) and "recompiles" in doc["derived"]
    rows = {r["bucket"]: r for r in doc["rows"]}
    assert set(rows) == {4, 8}
    spec = {
        "bucket": numbers.Integral,
        "requests": numbers.Integral,
        "p50_ms": numbers.Real,
        "p99_ms": numbers.Real,
        "qps": numbers.Real,
        "recompiles": numbers.Integral,
    }
    for b, row in rows.items():
        _assert_row(row, spec, f"serve[{b}]")
        assert row["recompiles"] == 0, row
        assert row["requests"] >= b
        assert row["p99_ms"] >= row["p50_ms"] > 0
        assert row["qps"] > 0


def test_ckpt_json_schema(tmp_path):
    """BENCH_ckpt.json: the fault-tolerance substrate rows (ISSUE 8) —
    blocking/async save, verified restore and the corrupt-latest
    fallback restore, all timed through the real CheckpointManager; the
    ``unverified_loads`` column on the restore rows is pinned to ZERO
    (the fallback ladder never loads bytes that failed manifest
    verification — the trajectory check's zero-baseline rule gates it
    in CI), and the async stall must not exceed the blocking save."""
    doc = _run_bench(
        tmp_path, "benchmarks.kernel_bench",
        "--mode", "ckpt", "--ckpt-elems", "65536",
    )
    assert set(doc) == {"mode", "rows", "derived"}
    assert doc["mode"] == "ckpt"
    assert isinstance(doc["derived"], str)
    assert "unverified_loads=0" in doc["derived"]
    rows = {r["stage"]: r for r in doc["rows"]}
    assert set(rows) == {
        "save_blocking", "save_async_stall", "save_async_total",
        "restore_verify", "restore_fallback",
    }
    spec = {
        "stage": str,
        "elems": numbers.Integral,
        "wall_ms": numbers.Real,
    }
    for stage, row in rows.items():
        _assert_row(row, spec, f"ckpt[{stage}]")
        assert row["wall_ms"] > 0, row
        assert row["elems"] == 65536
    for stage in ("restore_verify", "restore_fallback"):
        assert rows[stage]["unverified_loads"] == 0, rows[stage]
    # The whole point of the async path: the step loop only pays the
    # host-snapshot stall, not the filesystem write.
    assert (
        rows["save_async_stall"]["wall_ms"]
        <= rows["save_blocking"]["wall_ms"]
    )


def test_lm_loss_json_schema(tmp_path):
    """BENCH_lm_loss.json: one LM-head training step, three losses —
    all three rows present with throughput/peak columns and the
    machine-independent ``*_vs_naive`` ratios the trajectory check
    gates; the gradcheck block (the real Pallas linear kernel vs the
    dense oracle, softcap on AND off) passes its documented
    tolerances; peak loss-side elements shrink vs naive CE."""
    doc = _run_bench(
        tmp_path, "benchmarks.kernel_bench",
        "--mode", "lm-loss",
        "--positions", "128", "--catalog", "2048", "--d", "16",
    )
    assert set(doc) == {"mode", "rows", "derived", "gradcheck"}
    assert doc["mode"] == "lm-loss"
    assert isinstance(doc["derived"], str) and "tokens/s" in doc["derived"]
    rows = {r["loss"]: r for r in doc["rows"]}
    assert set(rows) == {"ce", "ce_fused_linear", "sce"}
    spec = {
        "loss": str,
        "tokens": numbers.Integral,
        "vocab": numbers.Integral,
        "d": numbers.Integral,
        "wall_us": numbers.Real,
        "tokens_per_s": numbers.Real,
        "peak_loss_elems": numbers.Integral,
        "tokens_per_s_vs_naive": numbers.Real,
        "peak_elems_vs_naive": numbers.Real,
    }
    for name, row in rows.items():
        _assert_row(row, spec, f"lm_loss[{name}]")
    assert rows["ce"]["tokens_per_s_vs_naive"] == pytest.approx(1.0)
    assert rows["ce"]["peak_loss_elems"] == 128 * 2048
    for name in ("ce_fused_linear", "sce"):
        assert rows[name]["peak_elems_vs_naive"] < 1.0, name
    caps = set()
    for gc in doc["gradcheck"]:
        _assert_row(gc, {
            "loss_rel_err": numbers.Real,
            "dx_max_abs_err": numbers.Real,
            "dw_max_abs_err": numbers.Real,
            "passes_tolerances": bool,
        }, f"lm_loss.gradcheck[{gc.get('logit_softcap')}]")
        assert gc["passes_tolerances"], gc
        caps.add(gc["logit_softcap"])
    assert caps == {None, 30.0}


def test_pareto_losses_json_schema(tmp_path):
    """BENCH_pareto.json (ISSUE 9): the multi-loss Pareto sweep — every
    registry loss × every catalog present, one constant row key set
    (trajectory's schema check pins row key TUPLES), trained rows fully
    measured, analytic-only rows with honest nulls, and the
    machine-independent ``peak_elems_vs_naive`` column populated
    everywhere (ce pinned to exactly 1.0)."""
    doc = _run_bench(
        tmp_path, "benchmarks.pareto_losses",
        "--steps", "2", "--catalogs", "2000",
        "--analytic-catalogs", "8000",
    )
    assert set(doc) == {"mode", "steps", "rows", "derived"}
    assert doc["mode"] == "pareto-losses"
    assert doc["steps"] == 2
    assert isinstance(doc["derived"], str) and "ndcg sce/ce" in doc["derived"]
    losses = {
        "ce", "ce_chunked", "ce_fused_linear",
        "bce_plus", "gbce", "ce_minus", "ce_pop", "rece", "sce",
    }
    rows = {r["label"]: r for r in doc["rows"]}
    assert set(rows) == {
        f"{l}@{c}" for l in losses for c in (2000, 8000)
    }
    key_sets = {tuple(sorted(r)) for r in doc["rows"]}
    assert len(key_sets) == 1, key_sets  # constant schema for trajectory
    spec = {
        "label": str,
        "loss": str,
        "catalog": numbers.Integral,
        "n_positions": numbers.Integral,
        "d": numbers.Integral,
        "analytic_only": bool,
        "mem_elems": numbers.Integral,
        "peak_elems_vs_naive": numbers.Real,
    }
    for label, row in rows.items():
        _assert_row(row, spec, f"pareto[{label}]")
        if row["analytic_only"]:
            assert row["catalog"] == 8000
            for k in ("ndcg@10", "hr@10", "positions_per_s",
                      "train_time_s", "quality_impl"):
                assert row[k] is None, (label, k)
        else:
            assert row["catalog"] == 2000
            for k in ("ndcg@10", "hr@10", "positions_per_s", "train_time_s"):
                assert isinstance(row[k], numbers.Real), (label, k)
            assert row["positions_per_s"] > 0
    # naive CE is its own yardstick; the streaming losses beat it
    for c in (2000, 8000):
        assert rows[f"ce@{c}"]["peak_elems_vs_naive"] == pytest.approx(1.0)
        assert rows[f"rece@{c}"]["peak_elems_vs_naive"] < 1.0
        assert rows[f"sce@{c}"]["peak_elems_vs_naive"] < 1.0
    # the exact-CE family shares one honest quality run at smoke scale
    assert rows["ce@2000"]["quality_impl"] == "ce"
    assert rows["ce_chunked@2000"]["quality_impl"] == "ce"
    assert (
        rows["ce_chunked@2000"]["ndcg@10"] == rows["ce@2000"]["ndcg@10"]
    )


def test_pareto_alpha_beta_json_schema(tmp_path):
    """BENCH_pareto_ab.json (ISSUE 9): the SCE (α, β) sweep on the
    standard --steps/--json contract — full grid present, unique labels,
    and the gated ``peak_elems_vs_naive`` ratio on every row."""
    doc = _run_bench(
        tmp_path, "benchmarks.pareto_alpha_beta", "--steps", "1"
    )
    assert set(doc) == {"mode", "steps", "rows", "derived"}
    assert doc["mode"] == "pareto-alpha-beta"
    assert doc["steps"] == 1
    assert isinstance(doc["derived"], str) and "best" in doc["derived"]
    rows = doc["rows"]
    labels = [r["label"] for r in rows]
    assert len(labels) == len(set(labels)) == 12  # 3 alpha × 2 beta × 2 b_y
    spec = {
        "label": str,
        "alpha": numbers.Real,
        "beta": numbers.Real,
        "b_y": numbers.Integral,
        "mem_elems": numbers.Integral,
        "peak_elems_vs_naive": numbers.Real,
        "ndcg@10": numbers.Real,
    }
    for row in rows:
        _assert_row(row, spec, f"pareto_ab[{row.get('label')}]")
        # honest ratio: heavy (alpha, beta) corners may legitimately
        # EXCEED naive CE at this tiny catalog — only positivity is
        # structural
        assert row["peak_elems_vs_naive"] > 0, row["label"]
    assert min(r["peak_elems_vs_naive"] for r in rows) < 1.0
    assert {(r["alpha"], r["beta"]) for r in rows} == {
        (a, b) for a in (1.0, 2.0, 4.0) for b in (1.0, 4.0)
    }


def test_guard_json_schema(tmp_path):
    """BENCH_guard.json: the kernel-guardrail health snapshot (ISSUE 10)
    — one canary-verdict row per kernel with ``canary_failures`` pinned
    to ZERO, the preflight sweep row with ``preflight_uncaught`` pinned
    to ZERO (every config repairs or raises the structured error), and
    the sentinel probe row with detection complete and zero false
    positives on a healthy loss."""
    doc = _run_bench(tmp_path, "benchmarks.kernel_bench", "--mode", "guard")
    assert set(doc) == {"mode", "rows", "derived"}
    assert doc["mode"] == "guard"
    assert isinstance(doc["derived"], str)
    assert "canary_failures=0" in doc["derived"]
    rows = {r["label"]: r for r in doc["rows"]}
    kernel_rows = {
        k: v for k, v in rows.items()
        if k not in ("preflight", "sentinels")
    }
    assert set(kernel_rows) == {
        "sce_bucket", "sce_gather", "mips_topk", "fused_ce",
        "linear_sce", "eval_fused", "eval_topk", "flash_attention",
    }
    spec = {
        "label": str,
        "backend": str,
        "interpret": bool,
        "canaries": numbers.Integral,
        "canary_failures": numbers.Integral,
    }
    for name, row in kernel_rows.items():
        _assert_row(row, spec, f"guard[{name}]")
        assert row["canaries"] >= 1
        assert row["canary_failures"] == 0, row
    pf = rows["preflight"]
    _assert_row(pf, {
        "checked": numbers.Integral,
        "repaired": numbers.Integral,
        "rejected_structured": numbers.Integral,
        "preflight_uncaught": numbers.Integral,
    }, "guard[preflight]")
    assert pf["checked"] >= pf["repaired"] + pf["rejected_structured"]
    assert pf["rejected_structured"] >= 1  # the grid includes illegal cases
    assert pf["preflight_uncaught"] == 0, pf
    st = rows["sentinels"]
    _assert_row(st, {
        "nonfinite_seeded": numbers.Integral,
        "nonfinite_detected": numbers.Integral,
        "sentinel_misses": numbers.Integral,
        "sentinel_false_positives": numbers.Integral,
    }, "guard[sentinels]")
    assert st["nonfinite_detected"] == st["nonfinite_seeded"] >= 1
    assert st["sentinel_misses"] == 0
    assert st["sentinel_false_positives"] == 0
