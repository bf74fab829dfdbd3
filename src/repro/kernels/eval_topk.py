"""Streaming full-catalog rank-and-top-k — Pallas TPU kernel.

The evaluation twin of ``kernels/sce_bucket.py``: unsampled metrics
(HR@K / NDCG@K / COV@K, paper §4.1.2) need, per user, (a) the rank of the
held-out target among all ``C`` catalog scores and (b) the top-``K``
recommended ids — but NOT the scores themselves. Materializing the
``(B, C)`` score matrix (what ``core.metrics.evaluate_seqrec`` used to
do) is the exact ``O(B·C)`` blow-up SCE removes from the loss side.

This kernel streams the catalog embedding table through VMEM in
``(block_c, d)`` tiles and keeps only per-row running accumulators:

  * ``(topk_vals, topk_ids)`` — a ``(block_b, K)`` merge buffer updated
    per tile by the shared insertion merge of ``kernels/topk_merge.py``
    (max/min/where and a lane shift only — no sort, Mosaic-friendly;
    the same implementation drives the MIPS candidate-selection kernel
    ``kernels/mips_topk.py``);
  * ``(gt, eq)`` — counts of catalog scores strictly greater than /
    exactly equal to the target score, from which the caller derives the
    pessimistic-tie rank ``gt + max(eq - 1, 0)`` (see
    ``core.metrics.rank_of_target`` for the convention).

Peak live elements are ``O(B·(K + block_c))`` instead of ``O(B·C)``.

Tie order matches a dense ``jax.lax.top_k`` exactly: tiles arrive in
ascending-id order, the merge buffer keeps equal values in
ascending-global-id order (each entry goes after the equal values
already buffered), so ties always resolve toward the lower catalog id.

The target score is an INPUT. A gather-einsum (the ``fused_ce``
positive-term trick) is the cheap way to produce it, but measured on CPU
it differs from the tiled matmul's target column by 1 ulp on ~15% of
rows — enough to flip ``gt``/``eq`` by one. ``eval_tgt_scores`` (below)
therefore streams the same tiles with the same ``jnp.dot`` and extracts
each row's target column, which is bitwise-consistent with this kernel
by construction (see KERNELS.md §eval_topk).

Grid: ``(B/block_b, C/block_c)`` with the catalog dimension innermost /
sequential so the VMEM scratch accumulators carry across catalog tiles.
No backward pass — evaluation is inference-only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot, pad_to, sds
from repro.kernels.topk_merge import ID_PAD as _ID_PAD
from repro.kernels.topk_merge import buffer_lanes, insert_topk_tile

NEG_INF = -1e30


def _eval_kernel(
    tgt_ref,  # (block_b,) f32 target scores
    x_ref,  # (block_b, d)
    y_ref,  # (block_c, d)
    vals_ref,  # (block_b, k) f32 out
    ids_ref,  # (block_b, k) i32 out
    gt_ref,  # (block_b,) i32 out
    eq_ref,  # (block_b,) i32 out
    vals_scr,  # (block_b, k rounded up to 128 lanes) f32
    ids_scr,  # (block_b, k rounded up to 128 lanes) i32
    gt_scr,  # (block_b,) i32
    eq_scr,  # (block_b,) i32
    *,
    k: int,
    n_c_tiles: int,
    block_c: int,
    c_actual: int,
    c_lo: int,
    c_hi: int,
    id_offset: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_scr[...] = jnp.full_like(vals_scr, NEG_INF)
        ids_scr[...] = jnp.full_like(ids_scr, _ID_PAD)
        gt_scr[...] = jnp.zeros_like(gt_scr)
        eq_scr[...] = jnp.zeros_like(eq_scr)

    logits = dot(x_ref[...], y_ref[...].T)
    idx = j * block_c + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1
    )
    col = id_offset + idx
    # Mask padded-tail columns (idx ≥ C — their global ids may alias the
    # next catalog shard's range) and ids outside [c_lo, c_hi) —
    # padding / phantom rows are never recommended or counted in ranks.
    valid = jnp.logical_and(
        idx < c_actual, jnp.logical_and(col >= c_lo, col < c_hi)
    )
    s = jnp.where(valid, logits, NEG_INF)

    tgt = tgt_ref[...][:, None]  # (block_b, 1)
    gt_scr[...] += jnp.sum((s > tgt).astype(jnp.int32), axis=-1)
    eq_scr[...] += jnp.sum((s == tgt).astype(jnp.int32), axis=-1)

    # Merge this tile's scores into the running top-k buffer — the
    # shared insertion merge (ties → lowest global id, the dense
    # lax.top_k rule; see topk_merge.py).
    vals_scr[...], ids_scr[...] = insert_topk_tile(
        vals_scr[...], ids_scr[...], s, id_offset + j * block_c, k
    )

    @pl.when(j == n_c_tiles - 1)
    def _finalize():
        vals_ref[...] = vals_scr[:, :k].astype(vals_ref.dtype)
        ids_ref[...] = ids_scr[:, :k]
        gt_ref[...] = gt_scr[...]
        eq_ref[...] = eq_scr[...]


def _tgt_kernel(
    tid_ref,  # (block_b,) i32 target catalog ids
    x_ref,  # (block_b, d)
    y_ref,  # (block_c, d)
    out_ref,  # (block_b,) f32 out
    acc_scr,  # (block_b,) f32
    *,
    n_c_tiles: int,
    block_c: int,
    id_offset: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    logits = dot(x_ref[...], y_ref[...].T)
    col = (
        id_offset
        + j * block_c
        + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    )
    hit = col == tid_ref[...][:, None]
    acc_scr[...] += jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)

    @pl.when(j == n_c_tiles - 1)
    def _finalize():
        out_ref[...] = acc_scr[...]


def eval_topk(
    x,
    y,
    tgt_scores,
    k: int,
    *,
    block_b: int = 128,
    block_c: int = 512,
    c_lo: int = 0,
    c_hi: int | None = None,
    id_offset: int = 0,
    interpret: bool = False,
):
    """Streaming top-k + target rank counts over the full catalog.

    Parameters
    ----------
    x : (B, d) user/query states.
    y : (C, d) catalog embedding table (or a slice of it).
    tgt_scores : (B,) f32 score of each row's held-out target item
        (``einsum('bd,bd->b', x, y[targets])`` — computed by the caller).
    k : number of top items to keep per row.
    block_b, block_c : VMEM tile sizes (rows of x / rows of y per tile).
    c_lo, c_hi : half-open global-id range of *valid* catalog columns;
        columns outside it (padding id 0, phantom padded rows) are
        excluded from both the top-k and the rank counts. Defaults to
        ``[0, id_offset + C)``.
    id_offset : global id of ``y``'s first row (0 unless ``y`` is a
        catalog shard).

    Returns
    -------
    (vals, ids, gt, eq) :
        ``vals`` (B, k) f32 top-k scores, descending;
        ``ids`` (B, k) i32 matching global catalog ids (ties → lower id,
        exactly the dense ``lax.top_k`` rule);
        ``gt`` (B,) i32 count of valid scores ``> tgt_scores``;
        ``eq`` (B,) i32 count of valid scores ``== tgt_scores``
        (includes the target column itself).
    """
    n, d = x.shape
    c = y.shape[0]
    if c_hi is None:
        c_hi = id_offset + c
    if n == 0:  # fully-filtered eval batch — mirror the ref's empties
        return (
            jnp.zeros((0, k), jnp.float32),
            jnp.zeros((0, k), jnp.int32),
            jnp.zeros((0,), jnp.int32),
            jnp.zeros((0,), jnp.int32),
        )
    block_b = min(block_b, n)
    block_c = min(block_c, c)

    xp = pad_to(x, 0, block_b)
    yp = pad_to(y, 0, block_c)
    tp = pad_to(tgt_scores.astype(jnp.float32), 0, block_b)
    n_p, c_p = xp.shape[0], yp.shape[0]
    n_b, n_c = n_p // block_b, c_p // block_c

    kernel = functools.partial(
        _eval_kernel,
        k=k,
        n_c_tiles=n_c,
        block_c=block_c,
        c_actual=c,
        c_lo=c_lo,
        c_hi=c_hi,
        id_offset=id_offset,
    )
    vals, ids, gt, eq = pl.pallas_call(
        kernel,
        grid=(n_b, n_c),
        in_specs=[
            pl.BlockSpec((block_b,), lambda i, j: (i,)),
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b,), lambda i, j: (i,)),
            pl.BlockSpec((block_b,), lambda i, j: (i,)),
        ],
        out_shape=[
            sds((n_p, k), jnp.float32, tp, xp, yp),
            sds((n_p, k), jnp.int32, tp, xp, yp),
            sds((n_p,), jnp.int32, tp, xp, yp),
            sds((n_p,), jnp.int32, tp, xp, yp),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, buffer_lanes(k)), jnp.float32),
            pltpu.VMEM((block_b, buffer_lanes(k)), jnp.int32),
            pltpu.VMEM((block_b,), jnp.int32),
            pltpu.VMEM((block_b,), jnp.int32),
        ],
        interpret=interpret,
    )(tp, xp, yp)
    return vals[:n], ids[:n], gt[:n], eq[:n]


def eval_tgt_scores(
    x,
    y,
    targets,
    *,
    block_b: int = 128,
    block_c: int = 512,
    id_offset: int = 0,
    interpret: bool = False,
):
    """Each row's target-column score, extracted from the SAME streamed
    tile matmul ``eval_topk`` runs (same block sizes ⇒ bitwise-identical
    logits ⇒ exact ``gt``/``eq`` counts even under ties).

    Parameters
    ----------
    x : (B, d) user/query states.
    y : (C, d) catalog table (or shard; ``id_offset`` = first row's
        global id).
    targets : (B,) i32 global catalog id of each row's held-out item.
        Rows whose target falls outside ``y``'s id range contribute 0
        (so a ``psum`` over catalog shards assembles the exact value).

    Returns
    -------
    (B,) f32 target scores.
    """
    n, d = x.shape
    c = y.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.float32)
    block_b = min(block_b, n)
    block_c = min(block_c, c)
    xp = pad_to(x, 0, block_b)
    yp = pad_to(y, 0, block_c)
    tp = pad_to(targets.astype(jnp.int32), 0, block_b, value=-1)
    n_p, c_p = xp.shape[0], yp.shape[0]
    n_b, n_c = n_p // block_b, c_p // block_c

    out = pl.pallas_call(
        functools.partial(
            _tgt_kernel, n_c_tiles=n_c, block_c=block_c,
            id_offset=id_offset,
        ),
        grid=(n_b, n_c),
        in_specs=[
            pl.BlockSpec((block_b,), lambda i, j: (i,)),
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda i, j: (i,)),
        out_shape=sds((n_p,), jnp.float32, tp, xp, yp),
        scratch_shapes=[pltpu.VMEM((block_b,), jnp.float32)],
        interpret=interpret,
    )(tp, xp, yp)
    return out[:n]
