"""Analytic kernel-legality + VMEM-budget preflight (KERNELS.md §Guard).

Every production dispatch in ``kernels/ops.py`` runs its block
configuration through :func:`preflight` before the ``pallas_call``
fires. The checker knows, per kernel, the tile/scratch accounting the
wrapper will request, and enforces a small set of NAMED rules:

  ==================  =========================================  ========
  rule                what it pins                               outcome
  ==================  =========================================  ========
  unknown_kernel      kernel name is registered                  raise
  positive_dims       rows / cols / d / k are ≥ 1                raise
  dtype_supported     operand dtype ∈ {float32, bfloat16}        raise
  positive_block      block sizes are ≥ 1                        repair
  block_le_dim        block never exceeds its axis               repair*
  mxu_alignment       (TPU) blocks are (8, 128)-tile aligned
                      or cover the whole axis                    repair
  vmem_budget         (TPU) modeled double-buffered tile +
                      scratch bytes fit ``REPRO_GUARD_VMEM_MB``  repair,
                                                                 raise
  ==================  =========================================  ========

``repair`` means the config is rewritten to the nearest legal shape
(halving / rounding / clamping) and the caller proceeds with the
repaired blocks; ``raise`` means a structured
:class:`KernelPreflightError` naming the violated rule — never a deep
Mosaic/XLA stack. The repair is a fixed point: feeding a repaired
config back through :func:`preflight` yields no further repairs (the
property test in ``tests/test_guard.py`` pins this round-trip).

``block_le_dim`` (*) is a SILENT normalization — the kernels already
clamp ``block = min(block, dim)`` themselves, so recording it without
warning keeps existing block-sweep callers quiet while the result
object still documents what will actually execute.

The VMEM model reuses the repo's peak-element accounting style (the
``*_peak_elements`` machinery of ``core.losses`` / ``eval.streaming``):
input tiles are double-buffered at operand dtype, the logit tile and
the per-kernel carry scratch are f32. It only gates on real TPU
backends — CPU interpret mode has no VMEM, and silently resizing
blocks there would break the bitwise same-shape-gemm contracts the
differential tests pin.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

# TPU vector tiling: last dim lanes, second-minor sublanes (see
# /opt/skills/guides/pallas_guide.md §Tiling Constraints — f32 tiles
# are (8, 128); bf16 packs (16, 128) but 128-lane / 8-sublane
# alignment is the common legal denominator the repair targets).
LANE = 128
SUBLANE = 8

# Default on-chip budget for one kernel's working set. Real VMEM is
# ~16 MiB/core; leave headroom for Mosaic's own spills.
DEFAULT_VMEM_MB = 12.0

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}

# Fallback blocks used to repair non-positive requests (clamped to the
# axis): the defaults every wrapper in kernels/ ships with.
_DEFAULT_BLOCK_ROWS = 128
_DEFAULT_BLOCK_COLS = 512


class KernelPreflightError(ValueError):
    """A kernel config preflight failed on an unrepairable rule.

    ``rule`` names the violated legality rule (one of
    :data:`PREFLIGHT_RULES`) — the structured replacement for the deep
    Mosaic/XLA error the illegal config would otherwise produce."""

    def __init__(self, kernel: str, rule: str, message: str):
        super().__init__(f"[guard.preflight] {kernel}: rule {rule!r}: {message}")
        self.kernel = kernel
        self.rule = rule


@dataclasses.dataclass(frozen=True)
class Repair:
    """One auto-repair applied by preflight: ``field`` moved
    ``old -> new`` to satisfy ``rule``. ``silent`` marks normalizations
    the kernels perform themselves (no warning needed)."""

    rule: str
    field: str
    old: int
    new: int
    silent: bool = False


@dataclasses.dataclass
class PreflightResult:
    """Outcome of a passing preflight: the (possibly repaired) params
    the dispatch should execute with, plus the audit trail."""

    kernel: str
    backend: str
    params: Dict[str, int]  # rows/cols/d/k/block_rows/block_cols
    dtype: str
    repairs: List[Repair]
    vmem_bytes: int
    vmem_budget_bytes: int

    @property
    def blocks(self) -> Tuple[int, int]:
        return self.params["block_rows"], self.params["block_cols"]

    @property
    def loud_repairs(self) -> List[Repair]:
        return [r for r in self.repairs if not r.silent]


def _merge_elements(block_rows: int, block_cols: int, k: int) -> int:
    """The streaming top-k merge (``topk_merge.insert_topk_tile``): the
    lane-aligned (vals, ids) scratch and their loop carry, beside the
    tile's scores and the candidates left of them."""
    return (4 * block_rows * _round_up(k, LANE)
            + 2 * block_rows * block_cols)


def _scratch_elements(kernel: str, block_rows: int, block_cols: int,
                      k: Optional[int], d: int, rows: int) -> int:
    """f32 carry/scratch elements one grid step of ``kernel`` keeps
    live in VMEM (mirrors each wrapper's ``scratch_shapes``)."""
    kk = max(k or 0, 1)
    if kernel == "sce_bucket":
        return 2 * block_rows  # (m, s) online-LSE carries
    if kernel == "sce_gather":
        # The backward kernel, at whole 128-lane rows: the gathered
        # tile, the dY RMW rows and their accumulator, and the tile's
        # values in flight (the tile, its dY part, the same-row sum):
        # 6 × block_cols rows; the per-bucket dX accumulator and its
        # double-buffered output block, whose rows are the bucket's
        # padded to block_rows: 3 × that; the x block and the dX part in
        # flight: 2 × block_rows; plus the (m, s) carries. (Held to
        # Mosaic's accounting by tests/test_tpu_compile.py at d = 64 and
        # d = 2,048.)
        dl = _round_up(d, LANE)
        rows_p = _round_up(rows, block_rows)
        return (dl * (2 * block_rows + 3 * rows_p + 4 * block_cols)
                + 2 * block_rows)
    if kernel == "mips_topk":
        return _merge_elements(block_rows, block_cols, kk)
    if kernel == "fused_ce":
        return 2 * block_rows  # (m, s)
    if kernel == "linear_sce":
        return 3 * block_rows  # (m, s, pos)
    if kernel == "eval_fused":
        # the merge + (gt, eq, tgt, m, s) row carries — the same
        # O(B·(k + block)) streaming state eval_peak_elements models at
        # batch scale.
        return _merge_elements(block_rows, block_cols, kk) + 5 * block_rows
    if kernel == "eval_topk":
        return _merge_elements(block_rows, block_cols, kk) + 2 * block_rows
    if kernel == "flash_attention":
        # Rows are query blocks, columns key blocks, d the q/k width (v's
        # is no wider). Beyond the q and k tiles and one score tile, the
        # largest of the three kernels: forward — the v tile, the o and
        # log-sum-exp blocks (double-buffered; a (rows, 1) column takes
        # 128 lanes), the (m, l) columns and the accumulator; dq — the v
        # and dO tiles, the log-sum-exp and D columns, the dq block and
        # its accumulator; dk/dv — the v and dO tiles, the log-sum-exp
        # and D rows (8 sublanes), the dk and dv blocks and their
        # accumulators.
        br, bc = block_rows, block_cols
        fwd = 2 * bc * d + 3 * br * d + 4 * br * LANE
        dq = 2 * bc * d + 5 * br * d + 4 * br * LANE
        dkv = 2 * (br + bc) * d + 6 * bc * d + 4 * SUBLANE * br
        return max(fwd, dq, dkv)
    raise KernelPreflightError(kernel, "unknown_kernel",
                               f"no VMEM model registered for {kernel!r}")


# Kernels the preflight knows how to model. eval_topk covers both
# deprecated two-pass entry points (eval_topk / eval_tgt_scores).
KNOWN_KERNELS = (
    "sce_bucket", "sce_gather", "mips_topk", "fused_ce", "linear_sce",
    "eval_fused", "eval_topk", "flash_attention",
)

PREFLIGHT_RULES = (
    "unknown_kernel", "positive_dims", "dtype_supported",
    "positive_block", "block_le_dim", "mxu_alignment", "vmem_budget",
)


def vmem_budget_bytes() -> int:
    """The guard's modeled on-chip budget (``REPRO_GUARD_VMEM_MB``)."""
    mb = float(os.environ.get("REPRO_GUARD_VMEM_MB", DEFAULT_VMEM_MB))
    return int(mb * 2**20)


def modeled_vmem_bytes(kernel: str, *, block_rows: int, block_cols: int,
                       d: int, k: Optional[int] = None,
                       dtype: str = "float32",
                       rows: Optional[int] = None) -> int:
    """Double-buffered input tiles (operand dtype) + the f32 logit tile
    + the kernel's f32 carry scratch, in bytes. ``rows`` (default
    ``block_rows``) is the whole row axis, which the gather kernel's
    per-bucket dX accumulator spans."""
    ebytes = _DTYPE_BYTES.get(dtype, 4)
    tiles = (block_rows * d + block_cols * d) * ebytes * 2  # dbl-buffered
    logit = block_rows * block_cols * 4
    scratch = _scratch_elements(
        kernel, block_rows, block_cols, k, d,
        block_rows if rows is None else rows,
    ) * 4
    return tiles + logit + scratch


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _align_block(block: int, dim: int, mult: int) -> int:
    """Nearest legal TPU block: a multiple of ``mult`` or the whole
    axis. Idempotent: the result re-checks clean."""
    aligned = _round_up(block, mult)
    return dim if aligned >= dim else aligned


def preflight(
    kernel: str,
    *,
    rows: int,
    cols: int,
    d: int,
    block_rows: int,
    block_cols: int,
    dtype="float32",
    k: Optional[int] = None,
    backend: Optional[str] = None,
) -> PreflightResult:
    """Check (and auto-repair) one kernel launch config.

    ``rows``/``cols`` are the tiled row axis and the streamed
    catalog/candidate axis; ``d`` the model width; ``k`` the selection
    size where the kernel keeps a merge buffer. ``backend`` defaults to
    the current JAX backend; pass ``"tpu"`` explicitly to exercise the
    Mosaic-only rules (alignment, VMEM) off-device.

    Returns a :class:`PreflightResult` whose ``params`` are legal by
    construction, or raises :class:`KernelPreflightError` naming the
    violated rule.
    """
    if kernel not in KNOWN_KERNELS:
        raise KernelPreflightError(
            kernel, "unknown_kernel",
            f"not a registered kernel (known: {', '.join(KNOWN_KERNELS)})",
        )
    if backend is None:
        import jax

        backend = jax.default_backend()
    dtype = str(getattr(dtype, "name", dtype))
    if dtype not in _DTYPE_BYTES:
        raise KernelPreflightError(
            kernel, "dtype_supported",
            f"dtype {dtype!r} unsupported (f32 accumulation paths take "
            f"{sorted(_DTYPE_BYTES)})",
        )
    try:
        rows, cols, d = int(rows), int(cols), int(d)
        block_rows, block_cols = int(block_rows), int(block_cols)
        k = None if k is None else int(k)
    except (TypeError, ValueError) as e:
        raise KernelPreflightError(
            kernel, "positive_dims", f"non-integer dimension: {e}"
        ) from None
    for name, v in (("rows", rows), ("cols", cols), ("d", d)):
        if v < 1:
            raise KernelPreflightError(
                kernel, "positive_dims", f"{name}={v} must be >= 1"
            )
    if k is not None and k < 1:
        raise KernelPreflightError(
            kernel, "positive_dims", f"k={k} must be >= 1"
        )

    repairs: List[Repair] = []

    def _fix(rule, field, old, new, silent=False):
        if new != old:
            repairs.append(Repair(rule, field, old, new, silent))
        return new

    if block_rows < 1:
        block_rows = _fix("positive_block", "block_rows", block_rows,
                          min(_DEFAULT_BLOCK_ROWS, rows))
    if block_cols < 1:
        block_cols = _fix("positive_block", "block_cols", block_cols,
                          min(_DEFAULT_BLOCK_COLS, cols))
    # The wrappers clamp block = min(block, dim) themselves — record
    # what will execute without shouting about it.
    if block_rows > rows:
        block_rows = _fix("block_le_dim", "block_rows", block_rows, rows,
                          silent=True)
    if block_cols > cols:
        block_cols = _fix("block_le_dim", "block_cols", block_cols, cols,
                          silent=True)

    on_tpu = backend == "tpu"
    if on_tpu:
        if block_cols < cols and block_cols % LANE:
            block_cols = _fix("mxu_alignment", "block_cols", block_cols,
                              _align_block(block_cols, cols, LANE))
        if block_rows < rows and block_rows % SUBLANE:
            block_rows = _fix("mxu_alignment", "block_rows", block_rows,
                              _align_block(block_rows, rows, SUBLANE))

    budget = vmem_budget_bytes()
    vmem = modeled_vmem_bytes(kernel, block_rows=block_rows,
                              block_cols=block_cols, d=d, k=k, dtype=dtype,
                              rows=rows)
    if on_tpu:
        # Shrink the streamed axis first (it only costs more grid
        # steps), then the row axis, keeping tile alignment; a config
        # that overflows at the minimum tile is unrepairable.
        while vmem > budget:
            if block_cols > LANE:
                new = max(LANE, _round_up(block_cols // 2, LANE))
                block_cols = _fix("vmem_budget", "block_cols",
                                  block_cols, min(new, cols))
            elif block_rows > SUBLANE:
                new = max(SUBLANE, _round_up(block_rows // 2, SUBLANE))
                block_rows = _fix("vmem_budget", "block_rows",
                                  block_rows, min(new, rows))
            else:
                raise KernelPreflightError(
                    kernel, "vmem_budget",
                    f"modeled {vmem / 2**20:.1f} MiB exceeds budget "
                    f"{budget / 2**20:.1f} MiB even at the minimum "
                    f"({SUBLANE}, {LANE}) tile (d={d}, k={k}, "
                    f"dtype={dtype}); raise REPRO_GUARD_VMEM_MB or "
                    f"shrink d/k",
                )
            vmem = modeled_vmem_bytes(kernel, block_rows=block_rows,
                                      block_cols=block_cols, d=d, k=k,
                                      dtype=dtype, rows=rows)

    return PreflightResult(
        kernel=kernel,
        backend=backend,
        params={"rows": rows, "cols": cols, "d": d, "k": k,
                "block_rows": block_rows, "block_cols": block_cols},
        dtype=dtype,
        repairs=repairs,
        vmem_bytes=vmem,
        vmem_budget_bytes=budget,
    )
