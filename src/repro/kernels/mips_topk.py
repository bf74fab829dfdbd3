"""Streaming per-bucket MIPS top-k — Pallas TPU kernel.

The candidate-selection stage of SCE (Algorithm 1 lines 3–11) is a
batched approximate MIPS: per bucket center, the top-``b_y`` catalog
rows (and top-``b_x`` positions) by inner product. The paper's
implementation — and this repo's pure-jnp path — computes the dense
score matrix ``B @ Yᵀ`` ``(n_b, C)`` and runs ``lax.top_k`` over the
full catalog axis. At production catalogs that score matrix is *larger*
than the bucket-logit tensor the paper's memory argument optimizes
(``C = 10M, n_b = 1024`` → ~40 GB f32), so selection, not the loss,
becomes the peak.

This kernel is the selection twin of ``kernels/eval_topk.py``: it
streams the catalog through VMEM in ``(block_c, d)`` tiles and carries
only the ``(block_q, K)`` top-k merge buffer per bucket row. Each tile
is merged by ``topk_merge.insert_topk_tile``: only the tile entries
that beat the buffer's k-th value are inserted, so the merge's rounds
follow the data rather than ``K``. Tie order is bit-identical to a
dense ``lax.top_k`` (lowest index wins). Peak live score elements drop
from ``O(n_b·C)`` to ``O(n_b·(K + block_c))``.

One kernel covers both selection sides:

  * ``Y`` side — ``mips_topk(b, y, b_y)``: catalog candidates;
  * ``X`` side — ``mips_topk(b, x, b_x, valid=valid_mask)``: position
    selection, with padding positions excluded via the ``(N,)``
    validity vector (the streaming equivalent of the dense path's
    ``where(valid_mask, xp, NEG_INF)``).

Grid: ``(n_q/block_q, C/block_c)`` with the catalog dimension innermost
/ sequential so the VMEM merge buffer carries across catalog tiles.
Selection is non-differentiable (indices only) — no backward pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot, pad_to, sds
from repro.kernels.topk_merge import ID_PAD, buffer_lanes, insert_topk_tile

NEG_INF = -1e30


def _mips_kernel(
    q_ref,  # (block_q, d)
    y_ref,  # (block_c, d)
    valid_ref,  # (1, block_c) i32 — 1 on selectable rows, 0 on the tail
    vals_ref,  # (block_q, k) f32 out
    ids_ref,  # (block_q, k) i32 out
    vals_scr,  # (block_q, k rounded up to 128 lanes) f32
    ids_scr,  # (block_q, k rounded up to 128 lanes) i32
    *,
    k: int,
    n_c_tiles: int,
    block_c: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_scr[...] = jnp.full_like(vals_scr, NEG_INF)
        ids_scr[...] = jnp.full_like(ids_scr, ID_PAD)

    scores = dot(q_ref[...], y_ref[...].T)
    # Mask caller-invalidated rows (padding positions on the X side) and
    # the padded tail, whose validity pads with 0.
    s = jnp.where(valid_ref[...] > 0, scores, NEG_INF)

    vals_scr[...], ids_scr[...] = insert_topk_tile(
        vals_scr[...], ids_scr[...], s, j * block_c, k
    )

    @pl.when(j == n_c_tiles - 1)
    def _finalize():
        vals_ref[...] = vals_scr[:, :k]
        ids_ref[...] = ids_scr[:, :k]


def mips_topk(
    q,
    y,
    k: int,
    *,
    valid=None,
    block_q: int = 128,
    block_c: int = 512,
    id_offset: int = 0,
    interpret: bool = False,
):
    """Streaming per-row top-``k`` of ``q @ yᵀ`` without the ``(n_q, C)``
    score matrix.

    Parameters
    ----------
    q : (n_q, d) query rows (bucket centers ``B``).
    y : (C, d) item rows (catalog ``Y``, or model outputs ``X`` for the
        position-selection side — or a catalog shard, see
        ``id_offset``).
    k : top-k size; clamped to ``C`` (the ``lax.top_k``-compatible
        ``min(b_y, C)`` clip, so ``b_y > C`` callers work unchanged).
    valid : optional (C,) bool/int — rows with 0/False never selected
        (the X-side ``valid_mask``).
    block_q, block_c : VMEM tile sizes; peak live score elements are
        ``n_q·(block_c + 2k)`` instead of ``n_q·C``.
    id_offset : global id of ``y``'s first row (for catalog shards); may
        be traced. The kernel selects local ids and the offset is added
        to every real id afterwards — a constant shift, so the tie order
        (lower id wins) is unchanged.

    Returns
    -------
    (vals, ids) : ``(n_q, k)`` f32 scores descending and ``(n_q, k)``
        i32 global ids — bit-identical to
        ``lax.top_k(q @ y.T + masking, k)`` including tie order (lower
        id wins).
    """
    n_q, d = q.shape
    c = y.shape[0]
    k = min(k, c)
    block_q = min(block_q, n_q)
    block_c = min(block_c, c)

    if valid is None:
        valid = jnp.ones((c,), jnp.int32)
    qp = pad_to(q, 0, block_q)
    yp = pad_to(y, 0, block_c)
    # The validity vector rides as a (1, C) lane-major row so its
    # (1, block_c) blocks meet the TPU (8, 128) tiling rule.
    vp = pad_to(valid.astype(jnp.int32).reshape(1, c), 1, block_c)
    nq_p, c_p = qp.shape[0], yp.shape[0]
    n_i, n_j = nq_p // block_q, c_p // block_c

    kernel = functools.partial(
        _mips_kernel, k=k, n_c_tiles=n_j, block_c=block_c
    )
    vals, ids = pl.pallas_call(
        kernel,
        grid=(n_i, n_j),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            sds((nq_p, k), jnp.float32, qp, yp, vp),
            sds((nq_p, k), jnp.int32, qp, yp, vp),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, buffer_lanes(k)), jnp.float32),
            pltpu.VMEM((block_q, buffer_lanes(k)), jnp.int32),
        ],
        interpret=interpret,
        name="mips_topk",
    )(qp, yp, vp)
    ids = ids[:n_q]
    return vals[:n_q], jnp.where(ids == ID_PAD, ids, ids + id_offset)
