"""``repro.kernels.guard`` — the three-layer kernel guardrail subsystem
(KERNELS.md §Guard, DESIGN.md §9).

  1. **Preflight** (:mod:`.preflight`) — analytic legality + VMEM
     models run before every ``pallas_call``; illegal block configs are
     auto-repaired or raise a structured :class:`KernelPreflightError`.
  2. **Conformance** (:mod:`.conformance`) — adversarial differential
     canaries per kernel, executed against the ``ref.py`` oracles on
     the actual backend; ``ops.py`` consults the memoized verdicts. On
     TPU a failing kernel raises; elsewhere it degrades to its ref path
     with a loud warning.
  3. **Sentinels** (:mod:`.sentinels`) — on-device NaN/Inf/degenerate-
     LSE counters threaded from the loss kernels into the train loop's
     divergence guard, so a strike names the kernel that went bad.

Policy knob (``REPRO_GUARD`` env / :func:`set_policy` /
``train.py --guard``):

  ========  =====================================================
  policy    behavior
  ========  =====================================================
  off       legacy dispatch — no preflight, no verdicts, no
            sentinels
  warn      (default) repair + degrade with a loud warning; train
            and serve keep running on the exact ref paths — except on
            TPU, where a failed canary always raises: the device path
            never silently becomes the XLA reference
  strict    unrepairable configs and failed conformance RAISE
            (:class:`KernelPreflightError` /
            :class:`KernelConformanceError`); serve refuses
            readiness until conformance passes
  ========  =====================================================
"""
from __future__ import annotations

import os
import warnings
from typing import Optional, Tuple

from repro.kernels.guard.conformance import (  # noqa: F401
    KernelConformanceError,
    Verdict,
    clear_verdicts,
    kernels,
    run_conformance,
    verdict_for,
    verdict_table,
)
from repro.kernels.guard.preflight import (  # noqa: F401
    KNOWN_KERNELS,
    PREFLIGHT_RULES,
    KernelPreflightError,
    PreflightResult,
    Repair,
    modeled_vmem_bytes,
    preflight,
    vmem_budget_bytes,
)
from repro.kernels.guard.sentinels import (  # noqa: F401
    describe_sentinels,
    loss_sentinels,
    merge_sentinels,
)

POLICIES = ("off", "warn", "strict")

_policy_override: Optional[str] = None


def policy() -> str:
    """Active guard policy: :func:`set_policy` override, else the
    ``REPRO_GUARD`` env var, else ``"warn"``."""
    p = _policy_override or os.environ.get("REPRO_GUARD", "warn")
    if p not in POLICIES:
        raise ValueError(
            f"guard policy {p!r} not in {POLICIES} (REPRO_GUARD?)"
        )
    return p


def set_policy(p: Optional[str]) -> None:
    """Set (or with ``None`` clear) the process-wide policy override —
    what ``train.py --guard`` and the drills use."""
    global _policy_override
    if p is not None and p not in POLICIES:
        raise ValueError(f"guard policy {p!r} not in {POLICIES}")
    _policy_override = p


def checked_blocks(
    kernel: str,
    *,
    rows: int,
    cols: int,
    d: int,
    block_rows: int,
    block_cols: int,
    dtype="float32",
    k: Optional[int] = None,
) -> Tuple[int, int]:
    """Preflight one dispatch → the (possibly repaired) block pair.

    The ``ops.py`` entry gate: under policy ``off`` the request passes
    through untouched; otherwise the config is checked, silently
    normalized where the kernel would do so anyway, LOUDLY repaired
    where it would otherwise die inside Mosaic, and raises a
    structured :class:`KernelPreflightError` when unrepairable.
    """
    if policy() == "off":
        return block_rows, block_cols
    if rows == 0:
        # Empty batch (e.g. a fully-filtered eval batch): every kernel
        # front-end early-returns empties without launching, so there
        # is no dispatch to preflight — and the positive_dims rule must
        # not reject a legal no-op.
        return block_rows, block_cols
    pf = preflight(
        kernel, rows=rows, cols=cols, d=d, block_rows=block_rows,
        block_cols=block_cols, dtype=dtype, k=k,
    )
    loud = pf.loud_repairs
    if loud:
        fixes = ", ".join(
            f"{r.field} {r.old}->{r.new} ({r.rule})" for r in loud
        )
        warnings.warn(
            f"[guard.preflight] {kernel}: auto-repaired illegal block "
            f"config: {fixes}",
            RuntimeWarning, stacklevel=3,
        )
    return pf.blocks


def kernel_enabled(kernel: str, *, interpret: Optional[bool] = None) -> bool:
    """Conformance gate for one dispatch.

    ``True`` → run the Pallas kernel. ``False`` → the canaries failed
    on a non-TPU backend and policy is ``warn``: the caller must degrade
    to its ref path (a loud ``RuntimeWarning`` has been emitted). Under
    ``strict``, and on TPU under any policy but ``off``, a failing
    verdict raises :class:`KernelConformanceError` instead.
    """
    pol = policy()
    if pol == "off":
        return True
    v = verdict_for(kernel, interpret=interpret)
    if v.passed:
        return True
    if pol == "strict" or v.backend == "tpu":
        raise KernelConformanceError(
            kernel, (v.backend, v.interpret), v.failures
        )
    warnings.warn(
        f"[guard.conformance] kernel {kernel!r} FAILED "
        f"{v.n_fail}/{v.n_fail + v.n_pass} canaries on backend "
        f"{v.backend} (interpret={v.interpret}) — DEGRADING to the "
        f"chunked ref path (exact, slower). Failures: "
        f"{'; '.join(v.failures)}",
        RuntimeWarning, stacklevel=3,
    )
    return False
