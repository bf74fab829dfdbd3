"""CPU tests of the benchmark's arithmetic, in seconds.

* the trace reduction, on a small trace recorded on a TPU v5e;
* the per-kernel work functions and model FLOPs, against hand counts
  at ``sasrec-gowalla``'s sizes;
* the peaks table refusing an unknown device;
* the serve traffic: the same seed gives the same arrivals and
  histories.

Run from the repository root: ``python -m pytest bench/tests/test_bench.py
-q``. It loads no TPU library and compiles no program. The whole-run
tests (the control and planted faults) are in ``test_runs.py``.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import peaks, trace, work  # noqa: E402

V5E = peaks.PEAKS["TPU v5 lite"]
GOWALLA = dict(d_model=64, d_ff=256, max_len=200, n_layers=2)


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------
def _recorded():
    with open(os.path.join(BENCH, "tests", "trace_v5e.json")) as f:
        rec = json.load(f)
    return [trace.Event(*e) for e in rec["events"]], rec


def test_reduce_recorded_trace():
    events, rec = _recorded()
    out = trace.reduce_events(events, rec["window_s"], rec["kernels"])
    want = rec["expected"]
    assert out["n_devices"] == 1
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for k, v in want["kernel_s"].items():
        assert out["kernel_s"][k] == pytest.approx(v, rel=1e-9)
    assert out["kernel_n"] == want["kernel_n"]
    assert [g[0] for g in out["idle_gaps"][:3]] == want["gap_labels"]


def test_reduce_by_hand():
    ev = [
        trace.Event("span", "", "bench.window", 0, 100),
        trace.Event("span", "", "bench.data", 10, 20),
        trace.Event("op", "/device:TPU:0", "%fusion.1 = f32[8] fusion()", 0, 10),
        trace.Event("op", "/device:TPU:0",
                    "%mips_topk.3 = (f32[8]) custom-call(%p.1)", 5, 10),
        trace.Event("op", "/device:TPU:0",
                    "%sce_gather_fwd.7 = f32[8] custom-call(%x)", 40, 20),
        # an operand named after a kernel is not that kernel
        trace.Event("op", "/device:TPU:0",
                    "%fusion.2 = f32[8] fusion(%mips_topk.3)", 90, 5),
    ]
    out = trace.reduce_events(ev, 100e-9, ["mips_topk", "sce_gather_fwd"])
    assert out["busy_s"] == pytest.approx(40e-9)  # [0,15] [40,60] [90,95]
    assert out["kernel_s"] == pytest.approx(
        {"mips_topk": 10e-9, "sce_gather_fwd": 20e-9})
    assert out["kernel_n"] == {"mips_topk": 1, "sce_gather_fwd": 1}
    # the 25 ns gap's midpoint (27.5) lies in bench.data, the 30 ns one's
    # (75) only in the window
    assert out["idle_gaps"] == [["bench.window", pytest.approx(30e-9)],
                                ["bench.data", pytest.approx(25e-9)]]
    assert out["device_ops"][0] == ["sce_gather_fwd.7", pytest.approx(20e-9)]
    assert trace.op_name("%mips_topk.3 = (f32[8]) custom-call()") == "mips_topk"


# ---------------------------------------------------------------------------
# Work functions, by hand at sasrec-gowalla's sizes
# ---------------------------------------------------------------------------
def test_mips_topk_work_by_hand():
    # catalog side: 320 centres x 173,520 rows x d 64
    flops, nbytes = work.mips_topk(320, 173_520, 64, 256)
    assert flops == 2 * 320 * 173_520 * 64 == 7_107_379_200
    # queries 81,920 B + catalog 44,421,120 B + 320 x 256 x 8 B out
    assert nbytes == 81_920 + 44_421_120 + 655_360
    # position side, with the (N,) bool mask
    assert work.mips_topk(320, 25_600, 64, 320, masked=True)[1] == (
        81_920 + 6_553_600 + 819_200 + 25_600)


def test_sce_gather_work_by_hand():
    f, b = work.sce_gather_fwd(320, 320, 256, 64)
    assert f == 3_355_443_200
    # x_b 26,214,400 + candidates 20,971,520 + ids 327,680 + 409,600
    # + partials 409,600
    assert b == 26_214_400 + 20_971_520 + 327_680 + 409_600 + 409_600
    f, b = work.sce_gather_bwd(320, 320, 256, 64)
    assert f == 2 * 3_355_443_200
    assert b == (26_214_400 + 20_971_520 + 737_280 + 819_200
                 + 26_214_400 + 41_943_040)


def test_step_flops_by_hand():
    # per position and layer: QKV 24,576 + out 8,192 + FFN 65,536
    # + causal attention 2·64·201 = 25,728 → 124,032; × 2 layers
    assert work.sasrec_forward_flops(GOWALLA, 1) == 248_064
    total = work.sce_train_step_flops(GOWALLA, 25_600, 173_520, 320, 320,
                                      256)
    assert total == (3 * 248_064 * 25_600       # encoder fwd + bwd
                     + 2 * 1_048_576_000        # Mix and position scores
                     + 7_107_379_200            # catalog scores
                     + 3 * 3_355_443_200)       # in-bucket fwd + bwd
    assert total == 38_322_176_000


def test_least_seconds_takes_the_binding_bound():
    # the catalog sweep is bound by bytes: 45.2 MB at 819 GB/s
    w = work.mips_topk(320, 173_520, 64, 256)
    assert work.least_seconds([w], V5E) == pytest.approx(w[1] / 819e9)
    assert work.least_seconds([(197e12, 0.0)], V5E) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v4")


# ---------------------------------------------------------------------------
# Serve traffic
# ---------------------------------------------------------------------------
def _read_config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_serve_traffic_follows_the_seed():
    from harness import traffic
    from kinds import serve

    mix = {"rate_per_s": 3200}
    due = traffic.arrivals(mix, 1.0, 2**31 + 7)
    assert due[0] == 0.0 and due[-1] < 1.0 and len(due) > 3000
    # every seed the same gaps in another order, so the same load
    assert abs(len(traffic.arrivals(mix, 1.0, 5))
               - len(traffic.arrivals(mix, 1.0, 6))) <= 1
    config = _read_config("sasrec-gowalla")
    a, lens = serve.histories(config, 1100, 2**31 + 7)
    b, _ = serve.histories(config, 1100, 2**31 + 7)
    assert a.shape == (1100, 200) and a.dtype == np.int32
    assert (a == b).all()
    assert lens.min() >= 100 and lens.max() <= 200
    # right-aligned: the padding (id 0) comes first
    assert all((row[:200 - n] == 0).all() and (row[200 - n:] > 0).all()
               for row, n in zip(a, lens))
    assert not (a == serve.histories(config, 1100, 8)[0]).all()
