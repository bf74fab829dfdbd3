"""The LM train cell's arithmetic (``bench/harness/work_lm.py``) by hand
at moonlight-16b-a3b's sizes, and the per-layer readers the cell
reports (``bench/metrics/``) on a made-up run of it: each reads the
right counters, sizes and trace entries, and returns nothing where the
run gives it nothing to read. CPU only; loads no TPU library."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import work_lm  # noqa: E402

CELL = "moonlight-16b-a3b.lm-train-8k"
with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b.json")) as f:
    CONFIG = json.load(f)
TOKENS = 8192
# one step's assignments to the 8 held experts over the 5 MoE layers:
# 5 × 8,192 tokens × 6 slots × 8/64
ROUTED = 30_720


def test_attention_flops_by_hand():
    # per token: projections 2·(2048·16·192 + 2048·576 + 512·16·256
    # + 16·128·2048) = 27,525,120; causal scores 2·16·192·4,096.5 =
    # 25,168,896 and values 2·16·128·4,096.5 = 16,779,264
    assert work_lm._attention_flops(CONFIG, TOKENS) == (
        TOKENS * (27_525_120 + 25_168_896 + 16_779_264))


def test_train_step_flops_by_hand():
    forward = (6 * 569_125_109_760        # latent attention, 6 layers
               + 1_133_871_366_144        # dense SwiGLU 2,048 → 11,264
               + 5 * 285_615_325_184      # shared experts and router
               + ROUTED * 17_301_504)     # held experts, 6·2,048·1,408
    assert work_lm.lm_forward_flops(CONFIG, TOKENS, ROUTED) == forward
    assert work_lm.lm_train_step_flops(
        CONFIG, TOKENS, ROUTED, 181, 181, 256) == (
        3 * forward
        + 12_146_704_384        # Mix projection and position scores
        + 15_183_380_480        # bucket scores against 20,480 rows
        + 103_057_195_008)      # in-bucket logits forward and backward


def test_held_experts_work_by_hand():
    flops, nbytes = work_lm.held_experts(CONFIG, ROUTED, 5)
    assert flops == 3 * ROUTED * 17_301_504
    # 5 layers × 8 experts × 3 matrices of 2,048 × 1,408 float32
    # = 1,384,120,320 B a pass; rows 30,720 × (2·2,048 + 3·1,408) × 4 B
    assert nbytes == 3 * (1_384_120_320 + 1_022_361_600)


def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# peaks at which the work's operations bind: the least time of a
# (flops, bytes) call is flops / 1e12 seconds
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}


def _run():
    """Two steps in a 4 s window, the trace's sums as a chip gives them."""
    counters = {"steps": 2, "window_s": 4.0, "data_s": 0.05,
                "tokens": TOKENS, "routed": 2 * ROUTED,
                "max_load": 2 * 5 * 1_000, "held": 8, "moe_layers": 5,
                "n_b": 181, "b_x": 181, "b_y": 256}
    trace = {"busy_s": 3.6, "window_s": 4.0, "n_devices": 1,
             "kernel_s": {"mips_topk": 0.1, "sce_gather_fwd": 0.2,
                          "sce_gather_bwd": 0.3},
             "layer_s": {"mla": 1.5, "moe/route": 0.1,
                         "moe/experts": 4.0}}
    return {"counters": counters, "trace": trace, "peaks": PEAKS,
            "config": CONFIG}


LM_READERS = {
    "mla_ms_per_step.lm-train-8k": 750.0,
    "moe_route_ms_per_step.lm-train-8k": 50.0,
    "moe_experts_ms_per_step.lm-train-8k": 2_000.0,
    # the largest held load 1,000 a layer and step over the mean 768
    "expert_load_max_over_mean.lm-train-8k": 1_000 / 768,
    # 2 steps × 1.594506608640 s of held-expert operations ÷ 4 s
    "moe_experts_roofline.lm-train-8k": 100 * 2 * 1.594506608640 / 4.0,
    "lm_step_mfu.lm-train-8k": (100 * 2 * 19_654_989_840_384 / 4.0
                                / 1e12),
    # the two sweeps: 6,073,352,192 + 15,183,380,480 operations a step
    "mips_topk_roofline.lm-train-8k": (100 * 2 * 21_256_732_672e-12
                                       / 0.1),
    # in-bucket logits 34,352,398,336 forward + 68,704,796,672 backward
    "sce_gather_roofline.lm-train-8k": (100 * 2 * 103_057_195_008e-12
                                        / 0.5),
    "data_ms_per_step.train": 25.0,
    "device_idle_share.train": 10.0,
}


def test_cell_reports_these_readers():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", [CELL])}
    assert names == set(LM_READERS)


@pytest.mark.parametrize("name", sorted(LM_READERS))
def test_lm_reader(name):
    read = _reader(name)
    assert read(_run()) == pytest.approx(LM_READERS[name])
    untraced = dict(_run(), trace=None)
    if name not in ("expert_load_max_over_mean.lm-train-8k",
                    "data_ms_per_step.train", "lm_step_mfu.lm-train-8k"):
        assert read(untraced) is None
    empty = _run()
    empty["trace"] = {"busy_s": 0.0, "window_s": 4.0, "n_devices": 0,
                      "kernel_s": {}, "layer_s": {}}
    empty["counters"] = dict(empty["counters"], steps=0, routed=0)
    assert read(empty) is None
