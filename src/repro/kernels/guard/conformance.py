"""On-backend conformance canaries: kernel vs chunked-ref oracle
(KERNELS.md §Guard).

Every Pallas kernel in this repo carries a small registry of
ADVERSARIAL differential cases — the exact shapes the ROADMAP's
Mosaic-validation item worries about:

  * tie-heavy duplicate catalog rows (top-k tie order: lower global id
    must win),
  * ``C % block`` tails (the padded last tile must stay masked),
  * starvation ``C < k`` (merge buffers larger than the catalog),
  * duplicate-row gather-indexed dY RMW (the ``sce_prefetch``
    ``input_output_aliases`` accumulation revisit),
  * softcap-active logit scales (the in-tile ``cap·tanh`` path),
  * continuous float32 data, within a tolerance that a bfloat16
    (single MXU pass) dot fails.

Every reference runs at HIGHEST matmul precision.

Each canary executes the REAL kernel entry point on the current
backend (Mosaic on TPU, interpret elsewhere) at Mosaic-legal block
shapes and compares against the pure-jnp ``kernels/ref.py`` oracle —
or, for the bitwise same-shape-gemm contract, against the sibling
kernel. A kernel that raises (a Mosaic miscompile surfacing as an
exception) or diverges numerically FAILS its canary; the
per-``(backend, interpret)`` verdict is memoized and consulted by every
``kernels/ops.py`` dispatch, which raises on TPU and elsewhere degrades
that kernel to its ref path with a loud warning.

Canaries resolve the kernel entry point by MODULE ATTRIBUTE at call
time (``_mod().fn(...)``), so a monkeypatched/broken kernel — the
fault-injection drills in ``tests/test_guard.py`` — is genuinely
exercised, not a captured healthy reference.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

_ATOL = 2e-4
_RTOL = 2e-4

_SEED = 0xCA9A  # canary inputs are deterministic per case


class KernelConformanceError(RuntimeError):
    """Strict-policy failure: a kernel's conformance canaries failed on
    this backend and the guard policy forbids silent degradation."""

    def __init__(self, kernel: str, backend_key, failures):
        msg = (
            f"[guard.conformance] kernel {kernel!r} FAILED conformance on "
            f"backend {backend_key}: " + "; ".join(failures)
        )
        super().__init__(msg)
        self.kernel = kernel
        self.failures = tuple(failures)


@dataclasses.dataclass(frozen=True)
class Verdict:
    """Outcome of one kernel's canary suite on one backend."""

    kernel: str
    backend: str
    interpret: bool
    n_pass: int
    n_fail: int
    failures: Tuple[str, ...]

    @property
    def passed(self) -> bool:
        return self.n_fail == 0

    def to_dict(self) -> Dict:
        return {
            "kernel": self.kernel,
            "backend": self.backend,
            "interpret": self.interpret,
            "passed": self.passed,
            "n_pass": self.n_pass,
            "n_fail": self.n_fail,
            "failures": list(self.failures),
        }


_CANARIES: Dict[str, List[Tuple[str, Callable[[bool], None]]]] = {}
_VERDICTS: Dict[Tuple[str, bool, str], Verdict] = {}
_LOCK = threading.RLock()


def _canary(kernel: str, name: str):
    def register(fn):
        _CANARIES.setdefault(kernel, []).append((name, fn))
        return fn

    return register


def _default_interpret() -> bool:
    from repro.kernels import ops as _ops

    return _ops._interpret_default()


def _backend_name() -> str:
    import jax

    return jax.default_backend()


def kernels() -> Tuple[str, ...]:
    """Kernel names with a registered canary suite."""
    return tuple(sorted(_CANARIES))


def clear_verdicts(kernel: Optional[str] = None) -> None:
    """Drop memoized verdicts (all, or one kernel's) — the hook the
    fault-injection drills and post-fix readiness refreshes use."""
    with _LOCK:
        if kernel is None:
            _VERDICTS.clear()
        else:
            for key in [k for k in _VERDICTS if k[2] == kernel]:
                del _VERDICTS[key]


def _run_canary_clean(fn, interpret: bool) -> Optional[BaseException]:
    """Run one canary on a FRESH thread and return its exception (or
    ``None`` on pass).

    A kernel's first guarded dispatch can happen while an outer
    jit/remat trace is active; JAX's trace state is thread-local, so a
    worker thread gives the canary a clean eager context — its concrete
    constants can't be lifted into the caller's trace (which would
    produce tracer-leak "failures" that have nothing to do with the
    kernel under test).
    """
    box: List[Optional[BaseException]] = [None]

    def worker():
        try:
            fn(interpret)
        except BaseException as e:  # noqa: BLE001 — a crash IS a verdict
            box[0] = e

    t = threading.Thread(target=worker, name="guard-canary", daemon=True)
    t.start()
    t.join()
    return box[0]


def verdict_for(kernel: str, *, interpret: Optional[bool] = None) -> Verdict:
    """Memoized canary verdict for ``kernel`` on the current backend.

    The first call per ``(backend, interpret, kernel)`` actually runs
    the canaries (small concrete inputs — safe even when reached from
    inside an outer trace); later calls are a dict lookup.
    """
    if kernel not in _CANARIES:
        raise KeyError(
            f"no conformance canaries registered for kernel {kernel!r} "
            f"(known: {', '.join(kernels())})"
        )
    if interpret is None:
        interpret = _default_interpret()
    backend = _backend_name()
    key = (backend, bool(interpret), kernel)
    with _LOCK:
        v = _VERDICTS.get(key)
        if v is not None:
            return v
        n_pass, failures = 0, []
        for name, fn in _CANARIES[kernel]:
            err = _run_canary_clean(fn, bool(interpret))
            if err is None:
                n_pass += 1
            else:
                failures.append(f"{name}: {type(err).__name__}: {err}")
        v = Verdict(kernel=kernel, backend=backend,
                    interpret=bool(interpret), n_pass=n_pass,
                    n_fail=len(failures), failures=tuple(failures))
        _VERDICTS[key] = v
        return v


def run_conformance(
    which: Optional[Tuple[str, ...]] = None,
    *,
    interpret: Optional[bool] = None,
    refresh: bool = False,
) -> Dict[str, Verdict]:
    """Run (or fetch memoized) canary suites → ``{kernel: Verdict}``.

    The startup/CI entry point: ``launch/serve.py`` runs it as a
    readiness gate, ``kernel_bench --mode guard`` snapshots it into
    ``BENCH_guard.json``.
    """
    names = tuple(which) if which else kernels()
    if refresh:
        for k in names:
            clear_verdicts(k)
    return {k: verdict_for(k, interpret=interpret) for k in names}


def verdict_table() -> List[Dict]:
    """JSON-ready snapshot of every memoized verdict (health endpoint /
    bench artifact format)."""
    with _LOCK:
        return [v.to_dict() for _, v in sorted(_VERDICTS.items())]


# ---------------------------------------------------------------------------
# Canary input builders
# ---------------------------------------------------------------------------
# Canary shapes are Mosaic-legal: row blocks of 8, column blocks of 128,
# d = 64 (sasrec-sce's width). The tie-order cases use small integers,
# so every dot product is exact at any matmul precision and the kernel
# and the XLA reference see identical scores — tie order is then a pure
# property of the kernel. The ``continuous_f32`` cases use normal data
# instead, and see the kernel's arithmetic. On a TPU v5e the kernels'
# float32 gradients sit ~3e-5 from float64 (sce_gather at sasrec-sce's
# widths; XLA's HIGHEST-precision reference ~1e-6), one-pass bfloat16
# dots ~2e-3; on the CPU, one bfloat16 dot in sce_gather's forward alone
# moves its dX by 1.5e-4. References run at HIGHEST precision: XLA's
# default on a TPU is one bfloat16 pass as well.
_D = 64
_BR, _BC = 8, 128  # row block, column block
_F32_TOL = 1e-4  # relative error allowed on continuous data


def _rng(salt: int) -> np.random.Generator:
    return np.random.default_rng(_SEED + salt)


def _ints(r, shape, scale=1.0):
    import jax.numpy as jnp

    return jnp.asarray(r.integers(-2, 3, size=shape) * scale, jnp.float32)


def _normals(r, shape, scale=1.0):
    import jax.numpy as jnp

    return jnp.asarray(r.standard_normal(shape) * scale, jnp.float32)


def _highest():
    import jax

    return jax.default_matmul_precision("highest")


def _assert_rel(name: str, got, want, tol=_F32_TOL):
    """Relative Frobenius error ``|got - want| / |want|`` within ``tol``
    (elementwise tolerances mislead on gradients, whose entries cancel)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise AssertionError(
            f"{name}: shape {got.shape} != oracle {want.shape}"
        )
    err = float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))
    if not err <= tol:
        raise AssertionError(
            f"{name}: relative error {err:.3e} vs the HIGHEST-precision "
            f"oracle (tol={tol})"
        )


def _assert_close(name: str, got, want, atol=_ATOL, rtol=_RTOL):
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(
            f"{name}: shape {got.shape} != oracle {want.shape}"
        )
    if not np.allclose(got, want, atol=atol, rtol=rtol, equal_nan=True):
        err = float(np.max(np.abs(got - want)))
        raise AssertionError(
            f"{name}: max abs err {err:.3e} vs oracle (atol={atol})"
        )


def _assert_ids(name: str, got, want):
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        raise AssertionError(f"{name}: id/tie-order mismatch vs oracle")


def _sce_inputs(salt: int, n_b=2, b_x=12, b_y=300, c=700, softcap=None,
                continuous=False):
    """Adversarial SCE bucket inputs: block tails on both bucket axes
    (b_x % 8, b_y % 128), padding slots (cand_id −1), a target-collision
    candidate, and duplicate gather rows inside one bucket and across
    buckets (the dY read-modify-write revisit). Small integers, or
    normals with logits of order one when ``continuous``."""
    import jax.numpy as jnp

    r = _rng(salt)
    scale = 4.0 if softcap else 1.0  # softcap-active logit magnitudes
    if continuous:
        x_b = _normals(r, (n_b, b_x, _D), scale / 4)
        y = _normals(r, (c, _D), 0.25)
    else:
        x_b = _ints(r, (n_b, b_x, _D), scale)
        y = _ints(r, (c, _D))
    tgt_b = jnp.asarray(r.integers(0, c, size=(n_b, b_x)), jnp.int32)
    idx = np.stack([r.permutation(c)[:b_y] for _ in range(n_b)])
    idx[:, 1] = idx[:, 0]  # duplicate row inside one bucket
    idx[1, 2:6] = idx[0, 2:6]  # rows shared by adjacent buckets
    cand_ids = idx.astype(np.int32)
    cand_ids[:, -1] = -1  # padding slot
    cand_ids[0, 7] = int(tgt_b[0, 0])  # forced target collision
    cand_ids = jnp.asarray(cand_ids)
    idx_y = jnp.asarray(np.maximum(np.asarray(cand_ids), 0), jnp.int32)
    with _highest():
        pos = jnp.einsum(
            "nxd,nxd->nx", x_b,
            jnp.take(y, tgt_b.reshape(-1), axis=0).reshape(n_b, b_x, _D),
        ).astype(jnp.float32)
    if softcap:
        pos = softcap * jnp.tanh(pos / softcap)
    y_b = jnp.take(y, idx_y.reshape(-1), axis=0).reshape(n_b, b_y, _D)
    return x_b, y, y_b, idx_y, tgt_b, cand_ids, pos


def _mod(name: str):
    # Resolved at CALL time so monkeypatched kernels are what runs.
    import importlib

    return importlib.import_module(f"repro.kernels.{name}")


# -- sce_bucket --------------------------------------------------------------
@_canary("sce_bucket", "tail_collisions_softcap")
def _sce_bucket_loss_canary(interpret: bool):
    from repro.kernels import ref

    for softcap in (None, 5.0):
        x_b, _, y_b, _, tgt_b, cand_ids, pos = _sce_inputs(
            1, softcap=softcap
        )
        got = _mod("sce_bucket").sce_bucket_loss(
            x_b, y_b, tgt_b, cand_ids, pos, _BR, _BC, interpret, softcap
        )
        with _highest():
            want = ref.sce_bucket_loss_ref(
                x_b, y_b, tgt_b, cand_ids, pos, softcap
            )
        _assert_close(f"loss(softcap={softcap})", got, want)


@_canary("sce_bucket", "plse_grad")
def _sce_bucket_plse_canary(interpret: bool):
    import jax

    from repro.kernels import ref

    x_b, _, y_b, _, tgt_b, cand_ids, _ = _sce_inputs(2)
    got = _mod("sce_bucket").sce_bucket_plse(
        x_b, y_b, tgt_b, cand_ids, _BR, _BC, interpret, None
    )
    with _highest():
        want = ref.sce_bucket_plse_ref(x_b, y_b, tgt_b, cand_ids, None)
    _assert_close("plse", got, want)

    def k_loss(xb):
        return _mod("sce_bucket").sce_bucket_loss(
            xb, y_b, tgt_b, cand_ids,
            jax.numpy.zeros(tgt_b.shape, jax.numpy.float32),
            _BR, _BC, interpret, None,
        ).sum()

    def r_loss(xb):
        return ref.sce_bucket_loss_ref(
            xb, y_b, tgt_b, cand_ids,
            jax.numpy.zeros(tgt_b.shape, jax.numpy.float32), None,
        ).sum()

    with _highest():
        want = jax.grad(r_loss)(x_b)
    _assert_close("dX", jax.grad(k_loss)(x_b), want, atol=1e-3, rtol=1e-3)


# -- sce_gather (manual-DMA candidate gather + dY RMW) -----------------------
@_canary("sce_gather", "duplicate_row_rmw")
def _sce_gather_canary(interpret: bool):
    import jax

    from repro.kernels import ref

    x_b, y, y_b, idx_y, tgt_b, cand_ids, pos = _sce_inputs(3)
    got = _mod("sce_prefetch").sce_gather_loss(
        x_b, y, idx_y, tgt_b, cand_ids, pos, _BR, _BC, interpret, None
    )
    with _highest():
        want = ref.sce_bucket_loss_ref(x_b, y_b, tgt_b, cand_ids, pos, None)
    _assert_close("gather_loss", got, want)

    # The RMW revisit: dY accumulated straight into (C, d) through
    # duplicated gather indices must equal the materialized-gather
    # oracle's scatter-add; dX rides the same backward kernel.
    def k_loss(xb, yy):
        return _mod("sce_prefetch").sce_gather_loss(
            xb, yy, idx_y, tgt_b, cand_ids, pos, _BR, _BC, interpret, None
        ).sum()

    def r_loss(xb, yy):
        y_b = jax.numpy.take(yy, idx_y.reshape(-1), axis=0).reshape(
            idx_y.shape + (yy.shape[-1],)
        )
        return ref.sce_bucket_loss_ref(
            xb, y_b, tgt_b, cand_ids, pos, None
        ).sum()

    gk = jax.grad(k_loss, argnums=(0, 1))(x_b, y)
    with _highest():
        gr = jax.grad(r_loss, argnums=(0, 1))(x_b, y)
    _assert_close("dX", gk[0], gr[0], atol=1e-3, rtol=1e-3)
    _assert_close("dY_rmw", gk[1], gr[1], atol=1e-3, rtol=1e-3)


@_canary("sce_gather", "plse_tail")
def _sce_gather_plse_canary(interpret: bool):
    from repro.kernels import ref

    x_b, y, y_b, idx_y, tgt_b, cand_ids, _ = _sce_inputs(4, b_y=260)
    got = _mod("sce_prefetch").sce_gather_plse(
        x_b, y, idx_y, tgt_b, cand_ids, _BR, _BC, interpret, None
    )
    with _highest():
        want = ref.sce_bucket_plse_ref(x_b, y_b, tgt_b, cand_ids, None)
    _assert_close("gather_plse", got, want)


# -- mips_topk ---------------------------------------------------------------
@_canary("mips_topk", "tie_duplicates_tail")
def _mips_ties_canary(interpret: bool):
    import jax.numpy as jnp

    from repro.kernels import ref

    r = _rng(10)
    # Tie-heavy catalog: every row duplicated, C=300 with block 128 →
    # tail of 44; ties must resolve toward the LOWER global id.
    y = jnp.repeat(_ints(r, (150, _D)), 2, axis=0)
    q = _ints(r, (12, _D))
    got_v, got_i = _mod("mips_topk").mips_topk(
        q, y, 16, block_q=_BR, block_c=_BC, interpret=interpret
    )
    with _highest():
        want_v, want_i = ref.mips_topk_ref(q, y, 16, chunk=_BC)
    _assert_ids("topk_ids", got_i, want_i)
    _assert_close("topk_vals", got_v, want_v)


@_canary("mips_topk", "starvation_valid_offset")
def _mips_starved_canary(interpret: bool):
    import jax.numpy as jnp

    from repro.kernels import ref

    r = _rng(11)
    q = _ints(r, (3, _D))
    y = _ints(r, (3, _D))
    valid = jnp.asarray([True, False, True])
    # k=8 > C=3 (starved merge buffer) + masked row + nonzero id base.
    got_v, got_i = _mod("mips_topk").mips_topk(
        q, y, 8, valid=valid, block_q=_BR, block_c=_BC, id_offset=7,
        interpret=interpret,
    )
    with _highest():
        want_v, want_i = ref.mips_topk_ref(
            q, y, 8, valid=valid, chunk=_BC, id_offset=7
        )
    _assert_ids("starved_ids", got_i, want_i)
    _assert_close("starved_vals", got_v, want_v)


# -- fused_ce ----------------------------------------------------------------
@_canary("fused_ce", "lse_and_loss_tail")
def _fused_ce_canary(interpret: bool):
    import jax.numpy as jnp

    from repro.kernels import ref

    r = _rng(20)
    x = _ints(r, (12, _D))
    y = _ints(r, (300, _D))  # C % 128 = 44
    tgt = jnp.asarray(r.integers(0, 300, size=(12,)), jnp.int32)
    got = _mod("fused_ce").fused_lse(x, y, _BR, _BC, interpret)
    got_loss = _mod("fused_ce").fused_ce_loss(x, y, tgt, _BR, _BC, interpret)
    with _highest():
        _assert_close("fused_lse", got, ref.fused_lse_ref(x, y))
        _assert_close("fused_ce_loss", got_loss,
                      ref.fused_ce_loss_ref(x, y, tgt))


# -- linear_sce --------------------------------------------------------------
@_canary("linear_sce", "softcap_value_and_grads")
def _linear_sce_canary(interpret: bool):
    import jax

    from repro.kernels import ref

    r = _rng(30)
    x = _ints(r, (12, _D), 3.0)
    w = _ints(r, (300, _D))
    tgt = jax.numpy.asarray(r.integers(0, 300, size=(12,)),
                            jax.numpy.int32)
    cap = 4.0  # softcap-active scales

    def k_loss(xx, ww):
        return _mod("linear_sce").linear_ce_loss(
            xx, ww, tgt, cap, _BR, _BC, interpret
        ).sum()

    def r_loss(xx, ww):
        return ref.linear_ce_loss_ref(
            xx, ww, tgt, logit_softcap=cap, chunk=_BC
        ).sum()

    (gl, (gdx, gdw)) = jax.value_and_grad(k_loss, argnums=(0, 1))(x, w)
    with _highest():
        (wl, (wdx, wdw)) = jax.value_and_grad(r_loss, argnums=(0, 1))(x, w)
    _assert_close("linear_ce", gl, wl)
    _assert_close("linear_dx", gdx, wdx, atol=1e-3, rtol=1e-3)
    _assert_close("linear_dw", gdw, wdw, atol=1e-3, rtol=1e-3)


# -- eval_fused --------------------------------------------------------------
@_canary("eval_fused", "ties_window_lse")
def _eval_fused_canary(interpret: bool):
    import jax.numpy as jnp

    from repro.kernels import ref

    r = _rng(40)
    base = _ints(r, (200, _D))
    y = jnp.concatenate([base, base[:100]], axis=0)  # C=300, ties, tail
    x = _ints(r, (12, _D))
    tgt = jnp.asarray(r.integers(1, 290, size=(12,)), jnp.int32)
    kw = dict(block_c=_BC, c_lo=1, c_hi=290, with_lse=True)
    got = _mod("eval_fused").eval_fused(
        x, y, tgt, 10, block_b=_BR, interpret=interpret, **kw
    )
    with _highest():
        want = ref.eval_fused_ref(x, y, tgt, 10, chunk=_BC, c_lo=1,
                                  c_hi=290, with_lse=True)
    for name, g, w in zip(("vals", "gt", "eq", "tgt", "m", "s"),
                          (got[0],) + got[2:], (want[0],) + want[2:]):
        _assert_close(f"eval_{name}", g, w)
    _assert_ids("eval_ids", got[1], want[1])


@_canary("eval_fused", "tgt_gather_bitwise")
def _eval_tgt_gather_canary(interpret: bool):
    import jax.numpy as jnp

    r = _rng(41)
    # Real-valued scores: the same-shape-gemm contract is that the
    # target slot of eval_tgt_gather equals, BITWISE, the column the
    # eval_fused sweep computes. Each row's target is planted as its
    # clear top-1 so the sweep reports that column's value.
    x = r.normal(size=(12, _D)).astype(np.float32)
    y = r.normal(size=(300, _D)).astype(np.float32)
    tgt = r.permutation(300)[:12].astype(np.int32)
    y[tgt] = 3.0 * x
    x, y, tgt = jnp.asarray(x), jnp.asarray(y), jnp.asarray(tgt)
    kw = dict(block_b=_BR, block_c=_BC, interpret=interpret)
    slot = _mod("eval_fused").eval_tgt_gather(x, y, tgt, **kw)
    vals, ids = _mod("eval_fused").eval_fused(x, y, tgt, 4, **kw)[:2]
    _assert_ids("planted_top1", ids[:, 0], tgt)
    _assert_close("tgt_gather_vs_sweep", slot, vals[:, 0],
                  atol=0.0, rtol=0.0)


# -- eval_topk (deprecated two-pass oracle entry points) ---------------------
@_canary("eval_topk", "two_pass_ties")
def _eval_topk_canary(interpret: bool):
    import jax.numpy as jnp

    from repro.kernels import ref

    r = _rng(50)
    base = _ints(r, (200, _D))
    y = jnp.concatenate([base, base[:100]], axis=0)  # C=300
    x = _ints(r, (12, _D))
    tgt = jnp.asarray(r.integers(0, 300, size=(12,)), jnp.int32)
    kw = dict(block_b=_BR, block_c=_BC, interpret=interpret)
    ts_got = _mod("eval_topk").eval_tgt_scores(x, y, tgt, **kw)
    got = _mod("eval_topk").eval_topk(x, y, ts_got, 10, **kw)
    with _highest():
        ts_want = ref.eval_tgt_scores_ref(x, y, tgt, chunk=_BC)
        want = ref.eval_topk_ref(x, y, ts_want, 10, chunk=_BC)
    _assert_close("tgt_scores", ts_got, ts_want, atol=0.0, rtol=0.0)
    _assert_ids("two_pass_ids", got[1], want[1])
    for name, g, w in zip(("vals", "gt", "eq"),
                          (got[0], got[2], got[3]),
                          (want[0], want[2], want[3])):
        _assert_close(f"two_pass_{name}", g, w)


# -- continuous float32 data: one case per kernel ------------------------------
@_canary("sce_bucket", "continuous_f32")
def _sce_bucket_f32_canary(interpret: bool):
    import jax

    from repro.kernels import ref

    x_b, _, y_b, _, tgt_b, cand_ids, pos = _sce_inputs(60, continuous=True)

    def k_loss(xb, yb):
        return _mod("sce_bucket").sce_bucket_loss(
            xb, yb, tgt_b, cand_ids, pos, _BR, _BC, interpret, None
        ).sum()

    def r_loss(xb, yb):
        return ref.sce_bucket_loss_ref(xb, yb, tgt_b, cand_ids, pos).sum()

    got = jax.value_and_grad(k_loss, argnums=(0, 1))(x_b, y_b)
    with _highest():
        want = jax.value_and_grad(r_loss, argnums=(0, 1))(x_b, y_b)
    for name, g, w in zip(("loss", "dX", "dY"), (got[0], *got[1]),
                          (want[0], *want[1])):
        _assert_rel(f"f32_{name}", g, w)


@_canary("sce_gather", "continuous_f32")
def _sce_gather_f32_canary(interpret: bool):
    import jax

    from repro.kernels import ref

    x_b, y, _, idx_y, tgt_b, cand_ids, pos = _sce_inputs(61, continuous=True)

    def k_loss(xb, yy):
        return _mod("sce_prefetch").sce_gather_loss(
            xb, yy, idx_y, tgt_b, cand_ids, pos, _BR, _BC, interpret, None
        ).sum()

    def r_loss(xb, yy):
        y_b = jax.numpy.take(yy, idx_y, axis=0)
        return ref.sce_bucket_loss_ref(xb, y_b, tgt_b, cand_ids, pos).sum()

    got = jax.value_and_grad(k_loss, argnums=(0, 1))(x_b, y)
    with _highest():
        want = jax.value_and_grad(r_loss, argnums=(0, 1))(x_b, y)
    for name, g, w in zip(("loss", "dX", "dY_rmw"), (got[0], *got[1]),
                          (want[0], *want[1])):
        _assert_rel(f"f32_{name}", g, w)


@_canary("mips_topk", "continuous_f32")
def _mips_f32_canary(interpret: bool):
    from repro.kernels import ref

    r = _rng(62)
    q, y = _normals(r, (12, _D)), _normals(r, (300, _D))
    got_v, got_i = _mod("mips_topk").mips_topk(
        q, y, 16, block_q=_BR, block_c=_BC, interpret=interpret
    )
    with _highest():
        want_v, want_i = ref.mips_topk_ref(q, y, 16, chunk=_BC)
    _assert_ids("f32_topk_ids", got_i, want_i)
    _assert_rel("f32_topk_vals", got_v, want_v)


@_canary("fused_ce", "continuous_f32")
def _fused_ce_f32_canary(interpret: bool):
    import jax.numpy as jnp

    from repro.kernels import ref

    r = _rng(63)
    x, y = _normals(r, (12, _D)), _normals(r, (300, _D), 0.25)
    tgt = jnp.asarray(r.integers(0, 300, size=(12,)), jnp.int32)
    got_lse = _mod("fused_ce").fused_lse(x, y, _BR, _BC, interpret)
    got_loss = _mod("fused_ce").fused_ce_loss(x, y, tgt, _BR, _BC, interpret)
    with _highest():
        _assert_rel("f32_fused_lse", got_lse, ref.fused_lse_ref(x, y))
        _assert_rel("f32_fused_ce_loss", got_loss,
                    ref.fused_ce_loss_ref(x, y, tgt))


@_canary("linear_sce", "continuous_f32")
def _linear_sce_f32_canary(interpret: bool):
    import jax

    from repro.kernels import ref

    r = _rng(64)
    x, w = _normals(r, (12, _D)), _normals(r, (300, _D), 0.5)
    tgt = jax.numpy.asarray(r.integers(0, 300, size=(12,)), jax.numpy.int32)
    cap = 4.0

    def k_loss(xx, ww):
        return _mod("linear_sce").linear_ce_loss(
            xx, ww, tgt, cap, _BR, _BC, interpret
        ).sum()

    def r_loss(xx, ww):
        return ref.linear_ce_loss_ref(
            xx, ww, tgt, logit_softcap=cap, chunk=_BC
        ).sum()

    got = jax.value_and_grad(k_loss, argnums=(0, 1))(x, w)
    with _highest():
        want = jax.value_and_grad(r_loss, argnums=(0, 1))(x, w)
    for name, g, v in zip(("loss", "dx", "dw"), (got[0], *got[1]),
                          (want[0], *want[1])):
        _assert_rel(f"f32_linear_{name}", g, v)


def _f64_counts(x, y, tgt, c_lo, c_hi):
    """Float64 gt counts: items in [c_lo, c_hi) scoring strictly above
    each row's target (continuous data: no exact ties)."""
    s = np.asarray(x, np.float64) @ np.asarray(y, np.float64).T
    t = np.asarray(tgt)
    own = s[np.arange(len(t)), t][:, None]
    return np.sum(s[:, c_lo:c_hi] > own, axis=1)


@_canary("eval_fused", "continuous_f32")
def _eval_fused_f32_canary(interpret: bool):
    import jax.numpy as jnp

    from repro.kernels import ref

    r = _rng(65)
    x, y = _normals(r, (12, _D)), _normals(r, (300, _D))
    tgt = jnp.asarray(r.integers(1, 290, size=(12,)), jnp.int32)
    got = _mod("eval_fused").eval_fused(
        x, y, tgt, 10, block_b=_BR, block_c=_BC, c_lo=1, c_hi=290,
        with_lse=True, interpret=interpret,
    )
    with _highest():
        want = ref.eval_fused_ref(x, y, tgt, 10, chunk=_BC, c_lo=1,
                                  c_hi=290, with_lse=True)
    _assert_ids("f32_eval_ids", got[1], want[1])
    _assert_ids("f32_eval_gt", got[2], _f64_counts(x, y, tgt, 1, 290))
    _assert_ids("f32_eval_eq", got[3], np.ones(12, np.int32))
    for name, g, w in zip(("vals", "tgt", "lse"),
                          (got[0], got[4], got[5] + np.log(got[6])),
                          (want[0], want[4], want[5] + np.log(want[6]))):
        _assert_rel(f"f32_eval_{name}", g, w)


@_canary("eval_topk", "continuous_f32")
def _eval_topk_f32_canary(interpret: bool):
    import jax.numpy as jnp

    from repro.kernels import ref

    r = _rng(66)
    x, y = _normals(r, (12, _D)), _normals(r, (300, _D))
    tgt = jnp.asarray(r.integers(0, 300, size=(12,)), jnp.int32)
    kw = dict(block_b=_BR, block_c=_BC, interpret=interpret)
    ts = _mod("eval_topk").eval_tgt_scores(x, y, tgt, **kw)
    got = _mod("eval_topk").eval_topk(x, y, ts, 10, **kw)
    with _highest():
        ts_want = ref.eval_tgt_scores_ref(x, y, tgt, chunk=_BC)
        want = ref.eval_topk_ref(x, y, ts_want, 10, chunk=_BC)
    _assert_rel("f32_tgt_scores", ts, ts_want)
    _assert_ids("f32_two_pass_ids", got[1], want[1])
    _assert_rel("f32_two_pass_vals", got[0], want[0])
    _assert_ids("f32_two_pass_gt", got[2], _f64_counts(x, y, tgt, 0, 300))


@_canary("flash_attention", "causal_gqa_fwd_bwd")
def _flash_attention_canary(interpret: bool):
    """Latent attention's widths (q/k 192, v 128) over two 128-blocks a
    side — a skipped, a full and two diagonal tiles — with two query
    heads on one key/value head; ``o`` and the three gradients against
    the float32 reference, at the MXU precision the dispatch would use
    (one bfloat16 pass leaves ~4e-3 relative error; a wrong mask or
    block index, order one)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    r = _rng(67)
    q, k = _normals(r, (1, 256, 2, 192)), _normals(r, (1, 256, 1, 192))
    v, w = _normals(r, (1, 256, 1, 128)), _normals(r, (1, 256, 2, 128))
    mxu = ops._mxu_dtype(interpret)
    tol = _F32_TOL if mxu == jnp.float32 else 2e-2

    def k_out(*qkv):
        return _mod("flash_attention").flash_attention(
            *qkv, scale=192**-0.5, block_q=128, block_kv=128,
            mxu_dtype=mxu, interpret=interpret)

    def r_out(*qkv):
        return ref.causal_attention_ref(*qkv, 192**-0.5)

    got = k_out(q, k, v), jax.grad(
        lambda *a: jnp.sum(k_out(*a) * w), (0, 1, 2))(q, k, v)
    with _highest():
        want = r_out(q, k, v), jax.grad(
            lambda *a: jnp.sum(r_out(*a) * w), (0, 1, 2))(q, k, v)
    for name, g, x in zip(("o", "dq", "dk", "dv"), (got[0], *got[1]),
                          (want[0], *want[1])):
        _assert_rel(f"flash_{name}", g, x, tol)
