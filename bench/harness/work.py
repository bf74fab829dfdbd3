"""Work the algorithm needs, from shapes: floating-point operations
and HBM bytes of each kernel call, and the model FLOPs of a train step,
an eval call and a served request.

Counts follow the algorithm, never one implementation: a score matmul
is ``2·rows·cols·d`` operations; inputs and outputs are counted once
at their logical width (float32 and int32 are 4 bytes, a bool mask 1);
lane padding, merge passes and recomputation are not counted, so a
roofline share stays valid whatever later implements the kernel.
The arithmetic of the sweep counts follows the program's
``benchmarks/kernel_bench.py`` (``_sweep_flops``, ``traffic_model``).
"""
from __future__ import annotations

from typing import Iterable, Tuple

F32 = 4
I32 = 4

Work = Tuple[float, float]  # (flops, bytes)


def mips_topk(rows_q: int, rows_c: int, d: int, k: int, *,
              masked: bool = False) -> Work:
    """One streaming top-``k`` of ``q @ yᵀ``: the score matmul; reads
    of the queries, the swept rows (and their bool mask) and writes of
    the ``(rows_q, k)`` values and ids."""
    flops = 2 * rows_q * rows_c * d
    nbytes = (rows_q * d + rows_c * d) * F32 + rows_q * k * (F32 + I32)
    if masked:
        nbytes += rows_c
    return flops, nbytes


def sce_gather_fwd(n_b: int, b_x: int, b_y: int, d: int) -> Work:
    """In-bucket partial logsumexp: ``(n_b, b_x, d) × (n_b, b_y, d)``
    logits; reads the bucket positions, the gathered candidate rows,
    their ids and the targets; writes the ``(n_b, b_x)`` partials."""
    flops = 2 * n_b * b_x * b_y * d
    nbytes = ((n_b * b_x * d + n_b * b_y * d) * F32
              + (n_b * b_y + n_b * b_x) * I32 + n_b * b_x * F32)
    return flops, nbytes


def sce_gather_bwd(n_b: int, b_x: int, b_y: int, d: int) -> Work:
    """Its backward: the two gradient matmuls (``dX = P·Y_b``,
    ``dY_b = Pᵀ·X_b``; logits not recomputed); reads what the forward
    read plus the incoming ``(n_b, b_x)`` cotangent and partials,
    writes ``dX`` and read-modify-writes the touched ``dY`` rows."""
    flops = 4 * n_b * b_x * b_y * d
    nbytes = ((n_b * b_x * d + n_b * b_y * d) * F32
              + (n_b * b_y + n_b * b_x) * I32 + 2 * n_b * b_x * F32
              + n_b * b_x * d * F32 + 2 * n_b * b_y * d * F32)
    return flops, nbytes


def eval_fused(rows: int, c: int, d: int, k: int) -> Work:
    """One fused eval sweep: the score matmul; reads the states, the
    catalog, the targets and their scores; writes the top-``k`` values
    and ids and the two rank counts."""
    flops = 2 * rows * c * d
    nbytes = ((rows * d + c * d) * F32 + rows * (I32 + F32)
              + rows * k * (F32 + I32) + 2 * rows * I32)
    return flops, nbytes


def eval_tgt_gather(rows: int, d: int) -> Work:
    """The target-score pre-stage: one ``d``-long dot per row; reads
    the states, the target rows and ids, writes the scores."""
    return 2 * rows * d, (2 * rows * d) * F32 + rows * (I32 + F32)


def least_seconds(works: Iterable[Work], peaks: dict) -> float:
    """The least time the chip could take for these calls: per call the
    larger of operations over peak FLOP/s and bytes over peak HBM
    bandwidth, summed over the calls."""
    return sum(max(f / peaks["bf16_flops_per_s"],
                   b / peaks["hbm_bytes_per_s"]) for f, b in works)


# ---------------------------------------------------------------------------
# Model FLOPs (matmuls only, no recomputation)
# ---------------------------------------------------------------------------
def sasrec_forward_flops(sizes: dict, positions: int) -> float:
    """SASRec encoder forward over ``positions`` positions of sequences
    of ``max_len``: per layer the QKV, output and FFN projections and
    causal attention (query ``l`` attends ``l + 1`` keys: on average
    ``(L + 1) / 2`` keys, twice — scores and values)."""
    d, ff, L = sizes["d_model"], sizes["d_ff"], sizes["max_len"]
    per_layer = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ff + 2 * d * (L + 1)
    return sizes["n_layers"] * per_layer * positions


def sce_train_step_flops(sizes: dict, positions: int, catalog: int,
                         n_b: int, b_x: int, b_y: int) -> float:
    """One SCE train step: encoder forward and backward (3× forward),
    the Mix projection ``Ω·X``, the bucket-vs-positions and
    bucket-vs-catalog scores, and the in-bucket logits forward and
    backward (3× forward)."""
    d = sizes["d_model"]
    return (3 * sasrec_forward_flops(sizes, positions)
            + 2 * n_b * positions * d
            + 2 * n_b * positions * d
            + 2 * n_b * catalog * d
            + 3 * 2 * n_b * b_x * b_y * d)


def retrieval_flops(sizes: dict, users: int, catalog: int) -> float:
    """Scoring ``users`` histories: the encoder forward over every
    position and one catalog-wide score row each."""
    return (sasrec_forward_flops(sizes, users * sizes["max_len"])
            + 2 * users * catalog * sizes["d_model"])
