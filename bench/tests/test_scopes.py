"""CPU tests of ``harness/scopes.py``, the reduction of the program's
named scopes and host spans, and of the per-layer readers that read it.

Run from the repository root: ``python -m pytest bench/tests/test_scopes.py
-q``. It loads no TPU library and compiles no program.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import scopes, trace  # noqa: E402

TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"


def _read(name):
    with open(os.path.join(BENCH, "tests", name)) as f:
        return json.load(f)


def _op(device, text, start, dur, scope):
    return scopes.Op("op", device, text, start, dur, scope)


def _span(name, start, dur):
    return trace.Event("span", "", name, start, dur)


@pytest.mark.parametrize("op_name,path", [
    ("jit(train_step)/transpose(jvp(backbone))/while/body/dot_general",
     "backbone/while/body"),
    ("jit(train_step)/jvp()/select/catalog/mips_topk/pallas_call",
     "select/catalog/mips_topk"),
    ("jit(train_step)/jvp()/gather_loss/nd,nd->n/dot_general", "gather_loss"),
    ("jit(train_step)/optim/jit(_where)/select_n", "optim"),
    ("jit(train_step)/jit(_threefry_split)/make_seqrec_train_step.<locals>"
     ".loss_and_grad/while/body/add", ""),
    ("jit(train_step)/le", ""),
    (None, ""),
])
def test_scope_of(op_name, path):
    assert scopes.scope_of(op_name) == path


def test_scope_s_by_hand():
    """A ``while`` that encloses its body's ops counts once; two devices
    are averaged; an op under no scope goes to ``unscoped_s``."""
    ev = [
        _span("bench.window", 0, 200),
        # device 0: a loop [0, 50] with body ops inside it, then a kernel
        _op(TPU0, "%while.1 = f32[8] while(%t)", 0, 50, "backbone/while"),
        _op(TPU0, "%fusion.1 = f32[8] fusion(%p)", 10, 20,
            "backbone/while/body"),
        _op(TPU0, "%fusion.2 = f32[8] fusion(%p)", 30, 10,
            "backbone/while/body"),
        _op(TPU0, "%mips_topk.3 = f32[8] custom-call(%q)", 60, 40,
            "select/catalog/mips_topk"),
        _op(TPU0, "%fusion.3 = u32[2] fusion(%k)", 100, 5, ""),
        # device 1: the same kernel longer, and the Mix matmul
        _op(TPU1, "%fusion.4 = f32[8] fusion(%x)", 0, 10, "select"),
        _op(TPU1, "%mips_topk.3 = f32[8] custom-call(%q)", 10, 80,
            "select/catalog/mips_topk"),
    ]
    out = scopes.reduce_scoped(ev, 200e-9, ["mips_topk"])
    assert out["scope_s"] == pytest.approx({
        "backbone": 25e-9,  # (50 + 0) / 2
        "backbone/while": 25e-9,
        "select": 65e-9,  # (40 + 90) / 2
        "select/catalog": 60e-9,  # (40 + 80) / 2
    })
    assert out["unscoped_s"] == pytest.approx(2.5e-9)
    # the old keys as trace.reduce_events computes them
    want = trace.reduce_events(ev, 200e-9, ["mips_topk"])
    for k in ("busy_s", "kernel_s", "kernel_n", "device_ops", "n_devices"):
        assert out[k] == want[k]


def test_idle_takes_the_innermost_span_of_either_family():
    ev = [
        _span("bench.window", 0, 1000),
        _span("bench.data", 100, 300),
        _span("repro.data.next_batch", 120, 250),
        _span("bench.sync", 600, 300),
        _op(TPU0, "%fusion.1 = f32[8] fusion(%p)", 0, 100, "backbone"),
        # gap [100, 300]: midpoint 200 in repro.data.next_batch
        _op(TPU0, "%fusion.2 = f32[8] fusion(%p)", 300, 100, "optim"),
        # gap [400, 420]: midpoint 410 in bench.window only
        _op(TPU0, "%fusion.3 = f32[8] fusion(%p)", 420, 200, "select"),
        # gap [620, 700]: midpoint 660 in bench.sync
        _op(TPU0, "%fusion.4 = f32[8] fusion(%p)", 700, 100, "select"),
    ]
    out = scopes.reduce_scoped(ev, 1000e-9)
    assert out["idle_by_span"] == pytest.approx({
        "repro.data.next_batch": 200e-9, "bench.sync": 80e-9,
        "bench.window": 20e-9})
    assert out["idle_gaps"][0] == ["repro.data.next_batch",
                                   pytest.approx(200e-9)]
    assert out["span_n"]["repro.data.next_batch"] == 1
    assert out["span_s"]["bench.data"] == pytest.approx(300e-9)


def test_old_recorded_trace_reduces_as_before():
    """The reduction's old keys on the recorded trace without scopes read
    what ``trace_v5e.json`` expects; no operation there has a scope."""
    rec = _read("trace_v5e.json")
    events = [trace.Event(*e) for e in rec["events"]]
    out = scopes.reduce_scoped(events, rec["window_s"], rec["kernels"])
    want = rec["expected"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["kernel_s"] == pytest.approx(want["kernel_s"], rel=1e-9)
    assert out["kernel_n"] == want["kernel_n"]
    assert out["scope_s"] == {}
    assert out["unscoped_s"] == pytest.approx(out["busy_s"], rel=1e-9)


def _scoped_recording():
    rec = _read("trace_v5e_scoped.json")
    events = [scopes.Op(*e) if e[0] == "op" else trace.Event(*e)
              for e in rec["events"]]
    return events, rec


def test_scoped_recorded_trace():
    """Two steps of a traced ``sasrec-gowalla.train`` window on a v5e with
    the program's scopes: the reduction reads what the recording
    expects, and the four scopes cover at least 95% of busy time."""
    events, rec = _scoped_recording()
    out = scopes.reduce_scoped(events, rec["window_s"], rec["kernels"])
    want = rec["expected"]
    for k in ("busy_s", "unscoped_s"):
        assert out[k] == pytest.approx(want[k], rel=1e-9)
    assert out["scope_s"] == pytest.approx(want["scope_s"], rel=1e-9)
    assert out["idle_by_span"] == pytest.approx(want["idle_by_span"],
                                                rel=1e-9)
    top = sum(out["scope_s"][s] for s in
              ("backbone", "select", "gather_loss", "optim"))
    assert top >= 0.95 * out["busy_s"]
    # the catalog selection is the larger of the two
    assert out["scope_s"]["select/catalog"] > out["scope_s"][
        "select/positions"]


def test_op_names_from_hlo():
    text = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "ENTRY %main {",
        '  %mips_topk.3 = (f32[8]) custom-call(%a), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(train_step)/jvp()/'
        'select/catalog/mips_topk/pallas_call" stack_frame_id=3}',
        "  %bitcast.1 = f32[8] bitcast(%b)",
        '  ROOT %fusion.2 = f32[8] fusion(%c), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(train_step)/optim/add"}',
        "}",
    ])
    assert scopes.op_names_from_hlo(text) == ("jit_train_step", {
        "mips_topk.3":
            "jit(train_step)/jvp()/select/catalog/mips_topk/pallas_call",
        "fusion.2": "jit(train_step)/optim/add",
    })


# ---------------------------------------------------------------------------
# The readers of the scopes and spans
# ---------------------------------------------------------------------------
def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


READERS = {
    "select_ms_per_step.train": 1e3 * 0.8 / 4,
    "gather_loss_ms_per_step.train": 1e3 * 0.08 / 4,
    "backbone_ms_per_step.train": 1e3 * 0.02 / 4,
    "optim_ms_per_step.train": 1e3 * 0.004 / 4,
    "data_span_ms_per_step.train": 1e3 * 0.012 / 4,
    "idle_in_data_ms_per_step.train": 1e3 * 0.02 / 4,
    "serve_queue_wait_ms.serve": 1e3 * 0.5 / 100,
    "serve_batch_fill.serve": 100.0 * 100 / 128,
    "serve_idle_in_host_ms.serve": 1e3 * (0.03 + 0.01) / 5,
    "eval_host_ms_per_call.eval": 1e3 * 0.06 / 3,
}


def _scoped_readings():
    t = {
        "busy_s": 0.9, "window_s": 1.0,
        "scope_s": {"select": 0.8, "select/catalog": 0.7,
                    "gather_loss": 0.08, "backbone": 0.02,
                    "optim": 0.004},
        "span_s": {"repro.data.next_batch": 0.012},
        "span_n": {"repro.data.next_batch": 4, "repro.serve.batch": 5},
        "idle_by_span": {"repro.data.next_batch": 0.02,
                         "repro.serve.run": 0.03, "repro.serve.pad": 0.01,
                         "repro.serve.idle": 0.5, "repro.eval.fold": 0.04,
                         "repro.eval.encode": 0.02, "bench.sync": 0.01},
    }
    c = {"steps": 4, "served": 100, "batch_rows": 128, "queue_wait_s": 0.5,
         "calls": 3}
    return {"trace": t, "counters": c}


def _unscoped_readings():
    """What a program without scopes, spans or batching counters gives:
    ``trace.reduce_events``'s keys and the serve and train drivers'
    counters as they are."""
    t = {"busy_s": 0.9, "window_s": 1.0, "kernel_s": {}, "kernel_n": {},
         "device_ops": [], "idle_gaps": [], "n_devices": 1}
    c = {"steps": 4, "served": 100, "calls": 3}
    return {"trace": t, "counters": c}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader(name):
    read = _reader(name)
    assert read(_scoped_readings()) == pytest.approx(READERS[name])
    assert read(_unscoped_readings()) is None
    assert read({"trace": None, "counters": {}}) is None
