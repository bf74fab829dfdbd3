"""CPU tests that drive whole runs at a small size, past the harness's
look for a chip: a sound run is correct, and the control (the reference
one precision step down, in the program's place) and each planted fault
of the program are judged not correct.

Run from the repository root: ``python -m pytest bench/tests/test_runs.py
-q`` (about a minute). It loads no TPU library and compiles no
full-size program.
"""
from __future__ import annotations

import copy
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cell, peaks  # noqa: E402

V5E = peaks.PEAKS["TPU v5 lite"]


# ---------------------------------------------------------------------------
# Whole runs at a small size, past the look for a chip
# ---------------------------------------------------------------------------
SMALL = {
    "sasrec-gowalla.train": ({"n_items": 1000, "max_len": 16}, {}),
    "sasrec-1m.train": ({"n_items": 1000, "max_len": 16}, {}),
    # the server's registered smoke sizes
    "sasrec-gowalla.serve": ({"n_items": 500, "max_len": 32, "d_model": 32,
                              "d_ff": 128},
                             {"shape": None, "rate_per_s": 100,
                              "check_requests": 32}),
    "sasrec-1m.eval": ({"n_items": 3000, "max_len": 16},
                       {"users_per_call": 64, "batches": 2}),
}


def _read(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _spec(workload):
    """The cell's files: through ``BENCHMARK.json`` where it lists the
    cell, else by their names (``<config>.<traffic>``) for a cell whose
    files wait for its entry."""
    try:
        return cell.load(ROOT, workload)
    except KeyError:
        config, traffic = workload.rsplit(".", 1)
        return cell.Spec(
            workload={"name": workload, "config": config,
                      "traffic": traffic, "chips": 1},
            config=_read("configs", config + ".json"),
            traffic=_read("traffic", traffic + ".json"),
            limits=_read("limits", workload + ".json"),
            end_to_end=[], per_layer=[])


def small_spec(workload):
    spec = _spec(workload)
    sizes, mix = SMALL[workload]
    spec.config = copy.deepcopy(spec.config)
    spec.config["sizes"].update(sizes)
    spec.traffic = dict(spec.traffic, **mix)
    return spec


def small_run(workload, seed=2**31 + 5, seconds=1.0):
    import jax

    with jax.default_matmul_precision("highest"):
        return cell.run(small_spec(workload), seed=seed, seconds=seconds,
                        trace=False, devices=jax.devices()[:1], peaks=V5E,
                        t_process=0.0)[0]


def _wrap_train_step(monkeypatch, fault):
    from repro.launch import steps as steps_lib

    make = steps_lib.make_seqrec_train_step

    def faulty(*a, **kw):
        step, opt, sce_cfg = make(*a, **kw)
        return (lambda p, o, b, k: fault(step, p, o, b, k)), opt, sce_cfg

    monkeypatch.setattr(steps_lib, "make_seqrec_train_step", faulty)


def test_train_sound_run_is_correct():
    res = small_run("sasrec-gowalla.train")
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def test_train_state_unchanged_is_caught(monkeypatch):
    def unchanged(step, p, o, b, k):
        return p, o, step(p, o, b, k)[2]

    _wrap_train_step(monkeypatch, unchanged)
    res = small_run("sasrec-gowalla.train")
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch_is_caught(monkeypatch):
    def half(step, p, o, b, k):
        v = b["valid"]
        return step(p, o, dict(b, valid=v.at[v.shape[0] // 2:].set(False)), k)

    _wrap_train_step(monkeypatch, half)
    assert not small_run("sasrec-gowalla.train")["correct"]


def test_serve_altered_answer_is_caught(monkeypatch):
    from repro.launch import steps as steps_lib

    make = steps_lib.make_seqrec_mips_serve_step

    def faulty(*a, **kw):
        serve = make(*a, **kw)

        def altered(params, tokens):
            vals, ids = serve(params, tokens)
            return vals, ids.at[0, 0].set(ids[0, 1])

        return altered

    monkeypatch.setattr(steps_lib, "make_seqrec_mips_serve_step", faulty)
    res = small_run("sasrec-gowalla.serve", seconds=2.0)
    assert not res["correct"]


def test_eval_altered_rank_is_caught(monkeypatch):
    from repro.eval import harness

    scores = harness.streaming_eval_scores

    def altered(*a, **kw):
        vals, ids, gt, eq, *rest = scores(*a, **kw)
        return (vals, ids, gt.at[0].add(3), eq, *rest)

    monkeypatch.setattr(harness, "streaming_eval_scores", altered)
    res = small_run("sasrec-1m.eval")
    assert not res["correct"]
    assert res["checks"]["score_gap"]["value"] > 0


# The control: the reference one precision step below the configuration's
# in the program's place, at a size the CPU holds and at which its gap
# shows. For the serve and eval cells that is ``high`` (three bfloat16
# passes). The train cells' numbers cannot see ``high``: SCE's selection
# sits at round-off from a tie, so the program's own runs already read
# what ``high`` adds (PERF.md §2, §6); the step after it, one bfloat16
# pass, is what they catch.
CONTROLS = {
    "sasrec-gowalla.serve": ({}, "high"),
    "sasrec-1m.eval": ({"n_items": 20_000}, "high"),
    "sasrec-gowalla.train": ({}, "bf16"),
    "sasrec-1m.train": ({}, "bf16"),
}


@pytest.mark.parametrize("workload", sorted(CONTROLS))
def test_control_is_not_correct(workload):
    import jax

    import control

    sizes, mode = CONTROLS[workload]
    spec = small_spec(workload)
    spec.config["sizes"].update(sizes)
    with jax.default_matmul_precision("highest"):
        numbers, _ = control.readings(spec, 4, mode, 10.0)
    ok, shown = cell.judge(numbers, spec.limits)
    assert not ok, shown
