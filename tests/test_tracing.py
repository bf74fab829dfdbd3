"""Profiler names of the program: the SASRec-SCE train step's named
scopes (``backbone``, ``select`` with ``select/positions`` and
``select/catalog``, ``gather_loss``, ``optim``) in its compiled HLO, the
host spans (``repro.data.*``, ``repro.train.*``, ``repro.serve.*``,
``repro.eval.*``) in a CPU profiler trace, and the serve worker's
batching counters in ``health()``. The same step compiled for a
described TPU v5e, with its Pallas kernels, is checked in
``test_tpu_compile.py``."""
import functools
import glob
import time

import jax
import numpy as np
import pytest

from _scopes import instructions, scope, seqrec_step_text, unscoped
from repro.data import (ClickDataConfig, ClickstreamDataset, Cursor,
                        LongTailConfig, LongTailDataset, SeqDataConfig,
                        SequenceDataset, ShardedCursor)


# ---------------------------------------------------------------------------
# Named scopes in the compiled train step
# ---------------------------------------------------------------------------
def test_cpu_train_step_ops_carry_the_four_scopes():
    """On the CPU the selections inside ``shard_map`` run the chunked
    references (a scan of tile matmuls and top-k), the in-bucket loss
    its jnp path."""
    text = seqrec_step_text()
    assert unscoped(text) == []
    found = {scope(op).split("/")[0] for *_, op in instructions(text) if op}
    assert {"backbone", "select", "gather_loss", "optim"} <= found
    matmuls = {scope(op).split("/")[1] for _, kind, _, _, op
               in instructions(text)
               if kind == "dot" and op and scope(op).startswith("select/")}
    assert {"positions", "catalog"} <= matmuls


# ---------------------------------------------------------------------------
# Host spans in a CPU profiler trace
# ---------------------------------------------------------------------------
def _spans(fn, tmp_path):
    """(name, start_ns, end_ns) of the ``repro.*`` and ``train`` host
    spans that ``fn()`` emits under a profiler trace."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro.") or e.name == "train":
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def _seq_data(cls=SequenceDataset, cfg_cls=SeqDataConfig, **kw):
    return cls(cfg_cls(n_items=200, seq_len=8, batch_size=4, **kw))


def _run_data():
    _seq_data().next_batch(Cursor(seed=0))


def _run_data_sharded():
    _seq_data().next_batch_sharded(
        ShardedCursor(Cursor(seed=0), host_id=1, n_hosts=2))


def _run_longtail():
    _seq_data(LongTailDataset, LongTailConfig).next_batch(Cursor(seed=0))


def _run_clickstream():
    ClickstreamDataset(ClickDataConfig(vocab_sizes=(50, 60), batch_size=8)
                       ).next_batch(Cursor(seed=0))


def _run_train():
    from repro.launch.train import train

    train("sasrec-sce", steps=2, batch=4, seq_len=16)


@functools.lru_cache(maxsize=1)
def _eval_inputs():
    from repro.models import sasrec

    cfg = sasrec.SeqRecConfig(n_items=300, max_len=20, d_model=16,
                              n_layers=1, n_heads=2, dropout=0.0)
    params = sasrec.init_params(jax.random.PRNGKey(0), cfg)
    eb, _ = SequenceDataset(SeqDataConfig(
        n_items=300, seq_len=20, batch_size=16)).eval_batch(Cursor(seed=0))
    return params, cfg, eb


def _run_eval():
    from repro.eval import evaluate_streaming

    evaluate_streaming(*_eval_inputs(), block_c=64)


_SERVER = None


def _server():
    global _SERVER
    if _SERVER is None:
        from repro.launch.serve import RetrievalServer

        _SERVER = RetrievalServer("sasrec-sce", buckets=(4, 8), top_k=3)
    return _SERVER


def _histories(n, seed=0):
    cfg = _server().cfg
    return np.random.default_rng(seed).integers(
        1, cfg.n_items, size=(n, cfg.max_len)).astype(np.int32)


def _run_serve():
    server = _server()
    for h in _histories(3):  # the worker idles between them
        server.submit(h).result(timeout=60)
        time.sleep(0.2)


@pytest.mark.parametrize("run,want", [
    (_run_data, {"repro.data.next_batch"}),
    (_run_data_sharded, {"repro.data.next_batch"}),
    (_run_longtail, {"repro.data.next_batch"}),
    (_run_clickstream, {"repro.data.next_batch"}),
    (_run_train, {"train", "repro.data.next_batch", "repro.train.place",
                  "repro.train.readback"}),
    (_run_eval, {"repro.eval.encode", "repro.eval.score",
                 "repro.eval.fold"}),
    (_run_serve, {"repro.serve.idle", "repro.serve.batch",
                  "repro.serve.pad", "repro.serve.run",
                  "repro.serve.reply"}),
], ids=["data", "data_sharded", "longtail", "clickstream", "train", "eval",
        "serve"])
def test_host_spans(run, want, tmp_path):
    run()  # compile and warm outside the trace
    spans = _spans(run, tmp_path)
    assert {s[0] for s in spans} == want
    if "repro.serve.batch" in want:  # pad, run, reply inside each batch
        batches = [s for s in spans if s[0] == "repro.serve.batch"]
        for name in ("repro.serve.pad", "repro.serve.run",
                     "repro.serve.reply"):
            for _, a, b in (s for s in spans if s[0] == name):
                assert any(a0 <= a and b <= b0 for _, a0, b0 in batches)


# ---------------------------------------------------------------------------
# Serve batching counters
# ---------------------------------------------------------------------------
def test_health_batching_counters_add_up():
    """Five requests queued while the worker is held back make one
    batch in bucket 8; a bulk ``score`` of three runs bucket 4."""
    server = _server()
    before = server.health()
    with server._cond:  # the worker cannot pick up until all five wait
        t0 = time.monotonic()
        reqs = [server.submit(h) for h in _histories(5, seed=1)]
        time.sleep(0.02)
    for r in reqs:
        r.result(timeout=60)
    held = time.monotonic() - t0
    server.score(_histories(3, seed=2))
    h = server.health()

    def delta(k):
        return h[k] - before[k]

    assert delta("served") == 8
    assert delta("batches") == 2
    assert delta("batch_rows") == 8 + 4
    waited = delta("queue_wait_s")
    assert 5 * 0.02 <= waited <= 5 * held
    assert 0.02 <= h["queue_wait_max_s"] and waited <= 5 * h[
        "queue_wait_max_s"]


def test_batching_counters_survive_concurrent_callers():
    """Bulk callers on four threads and the async worker count into the
    same counters under the server's lock: no update is lost."""
    import sys
    import threading

    server = _server()
    before = server.health()
    hist = _histories(3, seed=3)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [server.score(hist) for _ in range(10)])
            for _ in range(4)]
        for t in threads:
            t.start()
        reqs = [server.submit(h) for h in _histories(8, seed=4)]
        for t in threads:
            t.join(timeout=120)
        for r in reqs:
            r.result(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    h = server.health()
    async_batches = h["batches"] - before["batches"] - 40
    assert 1 <= async_batches <= 8
    assert h["served"] - before["served"] == 40 * 3 + 8
    # 40 bulk batches in bucket 4; an async batch of r requests runs in
    # bucket 4 or 8, at most r + 3 rows
    rows = h["batch_rows"] - before["batch_rows"] - 40 * 4
    assert 8 <= rows <= 8 + 3 * async_batches
