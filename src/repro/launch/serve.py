"""Retrieval server — MIPS-backed top-k over the (sharded) catalog.

The production serving leg of the ROADMAP north star: requests arrive
as user histories on an async bounded queue, a worker thread drains
them with continuous micro-batching into *padding-free shape buckets*
(one ahead-of-time compiled program per bucket — the jit-cache-
stability guarantee the fault-tolerance tests pin with a cache-miss
counter), and each micro-batch is scored by the same streaming
selection kernel the SCE training step uses (``kernels.ops.mips_topk``
via ``eval.streaming.streaming_topk``): the inference side never
materializes a ``(B, C)`` score matrix, and with a mesh the catalog
rides the ``model`` axis while request batches ride the data axes —
per-shard candidates merge through ``distributed_topk_from_local``
(ids + values cross the wire, never embeddings).

Dataflow (DESIGN.md §Serving)::

    submit() ──▶ bounded queue ──▶ worker: pop ≤ max_bucket requests
                 │ (backpressure:      │
                 │  ServerOverloaded-  ▼
                 │  Error when full)  bucket router → pad_to_bucket
                                       │
                                       ▼
                     AOT-compiled MIPS sweep for that bucket
                     (shard_map: catalog on "model", batch on data)
                                       │
                                       ▼
                     unpad → per-request ServeResult (full top-k, or
                     the degraded-k prefix under overload / past the
                     request deadline — never a hang, never a drop)

Params load through ``checkpoint/manager.py``
(``restore_params_latest`` with ``dist.sharding.seqrec_serve_shardings``
on a mesh) — a checkpoint written on any training mesh restores
straight into the serving layout. Random-init params are only the
documented ``ckpt_dir=None`` smoke path.

Profiler spans (``jax.profiler``; no-ops unless a trace is being
taken): ``repro.serve.idle`` while the worker waits on an empty queue,
``repro.serve.batch`` per micro-batch with ``repro.serve.pad`` (stack
and bucket padding), ``repro.serve.run`` (token transfer, the bucket's
program, readback) and ``repro.serve.reply`` (slicing and waking the
callers). ``health()`` adds the batching counters: ``batches``,
``batch_rows`` (summed bucket sizes), ``queue_wait_s`` and
``queue_wait_max_s`` (submit to pick-up by the worker).

Exactness: server top-k (ids, values, tie order) is bit-identical to
the dense masked ``lax.top_k`` oracle and to the fused eval scorer on
the same restored params (``tests/test_serve.py`` /
``tests/test_distributed.py``); only ids in ``[1, n_items)`` ever
serve.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch sasrec-sce \
      --requests 64 --buckets 8,32 [--ckpt-dir results/ckpt] \
      [--shape serve_p99]   # the full config instead of the smoke one
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import dataclasses
import functools
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data import Cursor, SeqDataConfig, SequenceDataset
from repro.dist.sharding import batch_spec, seqrec_serve_shardings
from repro.launch import steps as steps_lib
from repro.launch.cache import enable_compile_cache
from repro.launch.train import _init_params


class ServerOverloadedError(RuntimeError):
    """Backpressure rejection: the bounded queue is full, the server is
    closed, or the serve worker failed mid-batch. The request was NOT
    served — explicitly, never silently dropped."""


class ServerNotReadyError(RuntimeError):
    """Readiness rejection: the server has not passed its conformance
    readiness gate (``kernels/guard`` canaries for the serve kernel on
    this backend). Distinct from :class:`ServerOverloadedError` — this
    is a startup/health condition, not load; retrying without fixing
    or re-running conformance (``refresh_readiness``) will not help."""


# ---------------------------------------------------------------------------
# Shape-bucket padding (the shared helpers the old ad-hoc pad/slice
# arithmetic in ``score()`` grew into)
# ---------------------------------------------------------------------------
def pad_to_bucket(arr: np.ndarray, bucket: int, *, axis: int = 0) -> np.ndarray:
    """Zero-pad ``arr`` along ``axis`` up to exactly ``bucket`` rows —
    the static shape of one compiled bucket program. Raises
    ``ValueError`` when the rows don't fit (routing must split first)."""
    n = arr.shape[axis]
    if n > bucket:
        raise ValueError(
            f"{n} rows do not fit shape bucket {bucket}; split upstream"
        )
    if n == bucket:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, bucket - n)
    return np.pad(arr, widths)


def unpad(arr: np.ndarray, n: int, *, axis: int = 0) -> np.ndarray:
    """Drop bucket padding: the first ``n`` rows along ``axis`` (the
    inverse of :func:`pad_to_bucket` — ``unpad(pad_to_bucket(x, b), len(x))``
    is identity). Raises ``ValueError`` when ``n`` exceeds what's there."""
    if n > arr.shape[axis]:
        raise ValueError(f"cannot unpad {n} rows from {arr.shape[axis]}")
    idx = [slice(None)] * arr.ndim
    idx[axis] = slice(0, n)
    return arr[tuple(idx)]


class BucketRouter:
    """Maps arbitrary request-arrival counts onto a *static* set of
    batch-shape buckets, so the serving path only ever executes the
    ahead-of-time compiled programs (zero recompiles — the property
    test sweeps arrival sizes ``0..2·max_bucket`` against this)."""

    def __init__(self, buckets: Sequence[int]):
        bs = sorted({int(b) for b in buckets})
        if not bs or bs[0] <= 0:
            raise ValueError(f"need positive bucket sizes, got {buckets!r}")
        self.buckets: Tuple[int, ...] = tuple(bs)
        self.max_bucket: int = bs[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest bucket holding ``n`` requests (1 ≤ n ≤ max_bucket)."""
        if not 0 < n <= self.max_bucket:
            raise ValueError(
                f"n={n} outside (0, {self.max_bucket}]; plan() splits"
            )
        return self.buckets[bisect.bisect_left(self.buckets, n)]

    def plan(self, n: int) -> List[Tuple[int, int]]:
        """Split ``n`` pending requests into ``(count, bucket)`` chunks:
        full ``max_bucket`` batches, then one right-sized tail bucket.
        ``plan(0) == []``."""
        out: List[Tuple[int, int]] = []
        while n > self.max_bucket:
            out.append((self.max_bucket, self.max_bucket))
            n -= self.max_bucket
        if n:
            out.append((n, self.bucket_for(n)))
        return out


# ---------------------------------------------------------------------------
# Requests / results
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ServeResult:
    """One request's retrieval: ``k`` (item id, score) pairs, best
    first. ``degraded`` marks the smaller-k overload/deadline response
    (a prefix of the exact full top-k — still bit-exact, just fewer)."""

    ids: np.ndarray
    vals: np.ndarray
    degraded: bool
    k: int


class Request:
    """Handle returned by :meth:`RetrievalServer.submit`. ``result()``
    blocks until served, rejected (raises ``ServerOverloadedError``) or
    the caller-side ``timeout`` lapses (raises ``TimeoutError``)."""

    __slots__ = (
        "history", "deadline", "t_submit", "t_done",
        "_event", "_value", "_error",
    )

    def __init__(self, history: np.ndarray, deadline: Optional[float]):
        self.history = history
        self.deadline = deadline  # absolute time.monotonic(), or None
        self.t_submit = time.monotonic()
        self.t_done: Optional[float] = None
        self._event = threading.Event()
        self._value: Optional[ServeResult] = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        if not self._event.wait(timeout):
            raise TimeoutError(f"request not served within {timeout}s")
        if self._error is not None:
            raise self._error
        assert self._value is not None
        return self._value

    @property
    def latency_ms(self) -> Optional[float]:
        if self.t_done is None:
            return None
        return (self.t_done - self.t_submit) * 1e3

    def _finish(self, value: ServeResult) -> None:
        self.t_done = time.monotonic()
        self._value = value
        self._event.set()

    def _fail(self, err: BaseException) -> None:
        self.t_done = time.monotonic()
        self._error = err
        self._event.set()


# ---------------------------------------------------------------------------
# The server
# ---------------------------------------------------------------------------
class RetrievalServer:
    """Async micro-batching retrieval server over the MIPS serve step.

    Parameters
    ----------
    arch_name : seqrec arch (``configs.get_arch``).
    buckets : static batch-shape bucket set; one program is AOT-compiled
        per bucket at construction (``compile_count``), and serving a
        shape outside the set increments ``cache_misses`` (the tests
        pin it to 0). On a mesh every bucket must divide the data axes.
    top_k / degraded_top_k : full and overload/deadline answer sizes
        (degraded defaults to ``max(1, top_k // 2)``); the degraded
        response is a prefix of the exact top-k — recompile-free.
    queue_size : bounded-queue capacity; ``submit`` past it raises
        ``ServerOverloadedError``. Backlog ≥ ``queue_size // 2`` flips
        responses to degraded-k (graceful degradation under overload).
    deadline_s : default per-request deadline (relative seconds);
        requests whose deadline has lapsed by serve time get the
        degraded-k response instead of hanging or dropping.
    ckpt_dir : load params via ``CheckpointManager.restore_params_latest``
        (with ``seqrec_serve_shardings`` on a mesh). ``None`` = the
        random-init smoke path.
    mesh : optional ``Mesh`` — catalog on ``"model"``, requests on the
        data axes. ``None`` = single device.
    shape : optional registered ShapeSpec name: serve the arch's full
        config (``make_config``) instead of the smoke config.
    defer_readiness : skip the constructor's conformance readiness gate
        (``refresh_readiness``) — async submits then raise
        ``ServerNotReadyError`` until the gate is run and passes. Used
        by the fault-injection drills and by operators who want to run
        the gate on their own schedule.
    """

    def __init__(self, arch_name: str = "sasrec-sce", *,
                 buckets: Sequence[int] = (8, 32), top_k: int = 10,
                 degraded_top_k: Optional[int] = None, queue_size: int = 64,
                 deadline_s: Optional[float] = None,
                 ckpt_dir: Optional[str] = None, mesh=None,
                 seed: int = 0, block_c: int = 512,
                 defer_readiness: bool = False,
                 shape: Optional[str] = None):
        self.arch = get_arch(arch_name)
        assert self.arch.family == "seqrec", "serve.py serves seqrec archs"
        if shape is None:
            self.cfg = self.arch.make_smoke_config()
        else:
            self.arch.shape(shape)  # KeyError on an unregistered name
            self.cfg = self.arch.make_config(shape)
        self.mesh = mesh
        self.router = BucketRouter(buckets)
        self.top_k = int(top_k)
        self.degraded_top_k = (
            max(1, self.top_k // 2) if degraded_top_k is None
            else int(degraded_top_k)
        )
        if not 0 < self.degraded_top_k <= self.top_k:
            raise ValueError("need 0 < degraded_top_k <= top_k")
        self.queue_size = int(queue_size)
        self.default_deadline_s = deadline_s
        self.degrade_depth = max(1, self.queue_size // 2)

        self.restored_step: Optional[int] = None
        self.params = self._load_params(ckpt_dir, seed)

        step = steps_lib.make_seqrec_mips_serve_step(
            self.arch, self.cfg, mesh, top_k=self.top_k, block_c=block_c
        )
        if mesh is not None:
            from jax.sharding import NamedSharding

            self._tok_sharding = NamedSharding(mesh, batch_spec(mesh, 2))
            self._jitted = jax.jit(step, in_shardings=(
                seqrec_serve_shardings(self.cfg, mesh), self._tok_sharding
            ))
        else:
            self._tok_sharding = None
            self._jitted = jax.jit(step)

        # One AOT-compiled program per bucket; executing a Compiled can
        # never retrace, so cache_misses counts exactly the shapes that
        # escaped the static bucket set.
        self._compiled: Dict[int, Any] = {}
        self.compile_count = 0
        self.cache_misses = 0
        for b in self.router.buckets:
            self._compile_bucket(b)

        self._cond = threading.Condition()
        self._queue: deque[Request] = deque()
        self._closed = False
        self._worker: Optional[threading.Thread] = None
        self.served = 0
        self.degraded_served = 0
        self.rejected = 0
        # Batching counters, under ``_cond``: programs run and their
        # summed bucket rows (fill = served / batch_rows), and the time
        # async requests waited from submit to pick-up by the worker.
        self.batches = 0
        self.batch_rows = 0
        self.queue_wait_s = 0.0
        self.queue_wait_max_s = 0.0

        # Conformance readiness gate (kernels/guard): async submits are
        # rejected with ServerNotReadyError until the serve kernel's
        # canaries pass on this backend (skipped under policy "off").
        self._ready = False
        self.readiness_error: Optional[str] = None
        if not defer_readiness:
            self.refresh_readiness()

    # -- params / compilation ---------------------------------------------
    def _ctx(self):
        return jax.set_mesh(self.mesh) if self.mesh is not None else (
            contextlib.nullcontext()
        )

    def _load_params(self, ckpt_dir: Optional[str], seed: int):
        if ckpt_dir is None:  # smoke path: random init, no checkpoint
            params = _init_params(
                self.arch, self.cfg, jax.random.PRNGKey(seed)
            )
        else:
            shardings = (
                seqrec_serve_shardings(self.cfg, self.mesh)
                if self.mesh is not None else None
            )
            step, params = CheckpointManager(ckpt_dir).restore_params_latest(
                shardings=shardings
            )
            if params is None:
                raise FileNotFoundError(
                    f"no checkpoint to serve under {ckpt_dir!r}"
                )
            self.restored_step = step
            return params
        if self.mesh is not None:
            params = jax.device_put(
                params, seqrec_serve_shardings(self.cfg, self.mesh)
            )
        return params

    def _compile_bucket(self, bucket: int) -> None:
        tokens_abs = jax.ShapeDtypeStruct(
            (bucket, self.cfg.max_len), jnp.int32
        )
        with self._ctx():
            self._compiled[bucket] = self._jitted.lower(
                self.params, tokens_abs
            ).compile()
        self.compile_count += 1

    def _run(self, bucket: int, tokens_padded: np.ndarray):
        """Execute the bucket's compiled program → host (vals, ids)."""
        fn = self._compiled.get(bucket)
        if fn is None:  # a shape the router should never emit
            self.cache_misses += 1
            self._compile_bucket(bucket)
            fn = self._compiled[bucket]
        with self._cond:
            self.batches += 1
            self.batch_rows += bucket
        with jax.profiler.TraceAnnotation("repro.serve.run"):
            tokens = jnp.asarray(tokens_padded, jnp.int32)
            if self._tok_sharding is not None:
                tokens = jax.device_put(tokens, self._tok_sharding)
            with self._ctx():
                vals, ids = fn(self.params, tokens)
            return np.asarray(vals), np.asarray(ids)

    # -- readiness / health -------------------------------------------------
    def refresh_readiness(self) -> bool:
        """Run (or fetch) the conformance verdict for the serve kernel
        and update the readiness flag — the startup gate, and the hook
        a post-fix operator calls (after ``guard.clear_verdicts``) to
        re-admit traffic. Policy ``off`` skips the gate entirely."""
        from repro.kernels import guard

        if guard.policy() == "off":
            self._ready = True
            self.readiness_error = None
            return True
        v = guard.verdict_for("mips_topk")
        if v.passed:
            self._ready = True
            self.readiness_error = None
        else:
            self._ready = False
            self.readiness_error = (
                f"serve kernel 'mips_topk' failed {v.n_fail}/"
                f"{v.n_fail + v.n_pass} conformance canaries on backend "
                f"{v.backend} (interpret={v.interpret}): "
                + "; ".join(v.failures)
            )
        return self._ready

    @property
    def ready(self) -> bool:
        return self._ready

    def health(self) -> Dict[str, Any]:
        """JSON-ready liveness/readiness snapshot: the readiness flag
        (+ why not, when gated), guard policy, queue depth, worker
        liveness, serve counters and the full per-(backend, kernel)
        conformance verdict table."""
        from repro.kernels import guard

        with self._cond:
            queue_depth = len(self._queue)
            worker_alive = (
                self._worker is not None and self._worker.is_alive()
            )
            closed = self._closed
            batching = {
                "batches": self.batches,
                "batch_rows": self.batch_rows,
                "queue_wait_s": self.queue_wait_s,
                "queue_wait_max_s": self.queue_wait_max_s,
            }
        return {
            "ready": self._ready,
            "readiness_error": self.readiness_error,
            "guard_policy": guard.policy(),
            "closed": closed,
            "queue_depth": queue_depth,
            "queue_size": self.queue_size,
            "worker_alive": worker_alive,
            "served": self.served,
            "degraded_served": self.degraded_served,
            "rejected": self.rejected,
            "compile_count": self.compile_count,
            "cache_misses": self.cache_misses,
            **batching,
            "conformance": guard.verdict_table(),
        }

    # -- synchronous bulk path --------------------------------------------
    def score(self, histories: np.ndarray):
        """Bulk-serve ``(n, max_len)`` histories synchronously (the
        ``serve_bulk`` shape family): route through the bucket plan,
        pad, run, unpad. Returns ``(vals, ids)`` of shape (n, top_k)."""
        histories = np.asarray(histories, np.int32)
        n = histories.shape[0]
        out_vals, out_ids = [], []
        ofs = 0
        for count, bucket in self.router.plan(n):
            chunk = pad_to_bucket(histories[ofs:ofs + count], bucket)
            vals, ids = self._run(bucket, chunk)
            out_vals.append(unpad(vals, count))
            out_ids.append(unpad(ids, count))
            ofs += count
        self.served += n
        if not out_vals:
            return (np.zeros((0, self.top_k), np.float32),
                    np.zeros((0, self.top_k), np.int32))
        return np.concatenate(out_vals), np.concatenate(out_ids)

    # -- async path --------------------------------------------------------
    def submit(self, history: np.ndarray, *,
               deadline_s: Optional[float] = None) -> Request:
        """Enqueue one ``(max_len,)`` history; returns a :class:`Request`
        handle. Raises ``ServerOverloadedError`` immediately when the
        bounded queue is full or the server is closed, and
        ``ServerNotReadyError`` when the conformance readiness gate has
        not passed (``refresh_readiness``)."""
        history = np.asarray(history, np.int32)
        if history.shape != (self.cfg.max_len,):
            raise ValueError(
                f"history shape {history.shape} != ({self.cfg.max_len},)"
            )
        rel = deadline_s if deadline_s is not None else self.default_deadline_s
        deadline = time.monotonic() + rel if rel is not None else None
        req = Request(history, deadline)
        with self._cond:
            if self._closed:
                self.rejected += 1
                raise ServerOverloadedError("server is closed")
            if not self._ready:
                self.rejected += 1
                raise ServerNotReadyError(
                    "server has not passed its conformance readiness "
                    "gate — " + (self.readiness_error or
                                 "refresh_readiness() was never run")
                )
            if len(self._queue) >= self.queue_size:
                self.rejected += 1
                raise ServerOverloadedError(
                    f"queue full ({self.queue_size} pending); retry later"
                )
            self._queue.append(req)
            self._ensure_worker()
            self._cond.notify()
        return req

    def _ensure_worker(self) -> None:  # caller holds self._cond
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._worker_loop, name="serve-worker", daemon=True
            )
            self._worker.start()

    def _worker_loop(self) -> None:
        while True:
            with self._cond:
                if not self._queue and not self._closed:
                    with jax.profiler.TraceAnnotation("repro.serve.idle"):
                        while not self._queue and not self._closed:
                            self._cond.wait()
                if not self._queue and self._closed:
                    return
                batch: List[Request] = []
                while self._queue and len(batch) < self.router.max_bucket:
                    batch.append(self._queue.popleft())
                backlog = len(self._queue)
                now = time.monotonic()
                for r in batch:
                    wait = now - r.t_submit
                    self.queue_wait_s += wait
                    self.queue_wait_max_s = max(self.queue_wait_max_s, wait)
            try:
                self._serve_batch(
                    batch, overloaded=backlog >= self.degrade_depth
                )
            except BaseException as e:  # noqa: BLE001 — per-batch isolation
                err = ServerOverloadedError(
                    f"serve worker failed mid-batch ({e!r}); request "
                    f"rejected, not served — resubmit to retry"
                )
                err.__cause__ = e
                for r in batch:
                    if not r.done():
                        self.rejected += 1
                        r._fail(err)

    @functools.partial(jax.profiler.annotate_function,
                       name="repro.serve.batch")
    def _serve_batch(self, batch: List[Request], *, overloaded: bool) -> None:
        bucket = self.router.bucket_for(len(batch))
        with jax.profiler.TraceAnnotation("repro.serve.pad"):
            tokens = pad_to_bucket(
                np.stack([r.history for r in batch]), bucket
            )
        vals, ids = self._run(bucket, tokens)
        with jax.profiler.TraceAnnotation("repro.serve.reply"):
            now = time.monotonic()
            for i, req in enumerate(batch):
                expired = req.deadline is not None and now > req.deadline
                degraded = overloaded or expired
                k = self.degraded_top_k if degraded else self.top_k
                self.served += 1
                self.degraded_served += int(degraded)
                req._finish(ServeResult(
                    ids=ids[i, :k].copy(), vals=vals[i, :k].copy(),
                    degraded=degraded, k=k,
                ))

    def close(self, timeout: float = 10.0) -> None:
        """Stop serving: pending (not-yet-batched) requests are rejected
        with the backpressure error — never silently dropped; the
        in-flight micro-batch (if any) still completes."""
        with self._cond:
            self._closed = True
            pending = list(self._queue)
            self._queue.clear()
            self._cond.notify_all()
        for req in pending:
            self.rejected += 1
            req._fail(ServerOverloadedError(
                "server closed before the request was served"
            ))
        if self._worker is not None:
            self._worker.join(timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sasrec-sce")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--buckets", default="8,32",
                    help="comma-separated static batch buckets")
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--queue-size", type=int, default=64)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint/manager.py directory; omit for "
                         "random-init smoke params")
    ap.add_argument("--shape",
                    help="registered ShapeSpec name (e.g. serve_p99): "
                         "serve the arch's full config instead of the "
                         "smoke config")
    args = ap.parse_args()
    enable_compile_cache()

    buckets = tuple(int(b) for b in args.buckets.split(","))
    server = RetrievalServer(
        args.arch, buckets=buckets, top_k=args.top_k,
        queue_size=args.queue_size,
        deadline_s=(args.deadline_ms / 1e3
                    if args.deadline_ms is not None else None),
        ckpt_dir=args.ckpt_dir,
        shape=args.shape,
    )
    health = server.health()
    n_canary = sum(v["n_pass"] + v["n_fail"] for v in health["conformance"])
    print(f"readiness: ready={health['ready']} "
          f"(guard={health['guard_policy']}, {n_canary} canaries run)")
    if not health["ready"]:
        print(f"NOT READY: {health['readiness_error']}")
        sys.exit(3)
    data = SequenceDataset(SeqDataConfig(
        n_items=server.cfg.n_items,
        seq_len=server.cfg.max_len,
        batch_size=args.requests,
    ))
    batch, _ = data.next_batch(Cursor(seed=1))

    t0 = time.time()
    reqs = [server.submit(h) for h in batch["tokens"]]
    results = [r.result(timeout=600.0) for r in reqs]
    dt = time.time() - t0
    lats = sorted(r.latency_ms for r in reqs)
    p50 = lats[len(lats) // 2]
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
    src = (f"checkpoint step {server.restored_step}"
           if server.restored_step is not None else "random init (smoke)")
    print(f"served {args.requests} requests in {dt*1e3:.1f} ms "
          f"({args.requests/dt:.0f} req/s; p50 {p50:.1f} ms, "
          f"p99 {p99:.1f} ms; buckets={server.router.buckets}, "
          f"recompiles={server.cache_misses}; params: {src})")
    print(f"degraded: {server.degraded_served}, "
          f"rejected: {server.rejected}")
    print("first request top items:", results[0].ids[:5],
          "scores:", results[0].vals[:5])
    server.close()


if __name__ == "__main__":
    main()
