"""Shared streaming top-k merge recurrence (Mosaic-friendly, sort-free).

Every streaming top-k kernel — ``kernels/eval_fused.py`` (the
single-pass evaluation scorer), ``kernels/mips_topk.py`` (SCE candidate
selection) and the deprecated ``kernels/eval_topk.py`` oracle —
carries a ``(rows, K)`` running buffer across catalog tiles and merges
each tile's scores into it. Mosaic has no general sort, so the merge is ``K``
rounds (a rolled loop) of *first-occurrence argmax* built from
max/min/where/iota only: find the row max over the ``(K + tile)``-wide
concatenation of buffer and tile, locate its earliest position, emit
``(val, id)``, knock the position out with ``NEG_INF``, repeat.

Tie rule (the load-bearing property): ties resolve toward the earliest
concatenation position. Because the running buffer is kept in
descending-value / ascending-id-within-ties order and tiles arrive in
ascending-global-id order, the earliest position among equal values is
always the lowest global id — by induction over merges the final
selection is *bit-identical to a dense* ``lax.top_k`` (lowest index wins
among ties). ``dist.collectives.distributed_topk`` guarantees the same
rule, so dense, streaming, and sharded selections agree exactly.

Exhausted rows (max == ``NEG_INF``: fewer than ``K`` valid columns seen
so far) emit the ``ID_PAD`` placeholder instead of a duplicate real id,
matching what ``lax.top_k`` leaves in the id-padded buffer slots.

Cost note: the merge is ``O(K·(K + tile))`` VPU work per tile per row
block — cheap for eval-sized ``K`` (≤ ~100), a VPU cost that can
exceed the tile's matmul for selection-sized ``K = b_y`` (256+);
:func:`merge_topk_tile_bitonic` is the
output-identical ``O(log²)`` partial-sort prototype for that regime
(gated behind ``mips_topk(merge_impl="bitonic")``, no default flip
pending a real-TPU profile).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
ID_PAD = jnp.iinfo(jnp.int32).max


def merge_topk_tile(vals, ids, tile_vals, tile_ids, k: int):
    """Merge one tile of scores into the running top-k buffer.

    Parameters
    ----------
    vals : (rows, k) f32
        Running top-k values, descending; ``NEG_INF`` in unfilled slots.
    ids : (rows, k) i32
        Matching ids; ``ID_PAD`` in unfilled slots.
    tile_vals : (rows, t) f32
        This tile's scores, already masked (``NEG_INF`` on invalid
        columns).
    tile_ids : (rows, t) i32
        Global ids of the tile columns, ascending.
    k : int
        Buffer width (static).

    Returns
    -------
    (vals', ids') : the merged ``(rows, k)`` buffer, same invariants.
    """
    cat_v = jnp.concatenate([vals, tile_vals], axis=-1)
    cat_i = jnp.concatenate([ids, tile_ids], axis=-1)
    width = k + tile_vals.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, cat_v.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)

    # Every per-row quantity stays a (rows, 1) column (keepdims) and each
    # round lands in its output slot by a lane-iota select: Mosaic cannot
    # reshape a 1-D row vector into a column, so no stack/reshape occurs.
    # A rolled loop keeps the program size independent of k.
    def round_(r, carry):
        cat_v, out_v, out_i = carry
        m = jnp.max(cat_v, axis=-1, keepdims=True)
        first = jnp.min(
            jnp.where(cat_v == m, pos, width), axis=-1, keepdims=True
        )
        sel = pos == first
        sel_id = jnp.sum(jnp.where(sel, cat_i, 0), axis=-1, keepdims=True)
        sel_id = jnp.where(m == NEG_INF, ID_PAD, sel_id)
        out_v = jnp.where(slot == r, m, out_v)
        out_i = jnp.where(slot == r, sel_id, out_i)
        return jnp.where(sel, NEG_INF, cat_v), out_v, out_i

    _, out_v, out_i = jax.lax.fori_loop(
        0, k, round_,
        (cat_v, jnp.full_like(vals, NEG_INF), jnp.full_like(ids, ID_PAD)),
    )
    return out_v, out_i


def _precedes(va, ia, vb, ib):
    """The merge's total order: ``a`` comes before ``b`` iff its value
    is larger, or equal with the lower id — the dense ``lax.top_k``
    tie rule both merge implementations reproduce."""
    return jnp.logical_or(
        va > vb, jnp.logical_and(va == vb, ia < ib)
    )


def merge_topk_tile_bitonic(vals, ids, tile_vals, tile_ids, k: int):
    """Bitonic-partial-sort variant of :func:`merge_topk_tile` —
    identical outputs (values, ids, tie order, ``ID_PAD`` exhausted
    slots), different cost shape.

    The K-round merge runs ``K`` first-occurrence-argmax rounds of
    ``O(K + tile)`` VPU work — ``O(K·(K + tile))`` per tile, the named
    scaling concern at
    selection-sized ``K = b_y`` (KERNELS.md §mips_topk). This variant
    instead bitonic-sorts the ``(K + tile)``-wide concatenation on the
    composite key (value desc, id asc) and keeps the first ``K``
    lanes: ``O(log² W)`` compare-exchange stages of ``O(W)`` work each
    (``W`` = ``K + tile`` padded to a power of two) — ~55 stages at
    ``K = 256, tile = 512`` vs 256 rounds. Built from
    reshape/flip partner exchanges + max/min/where/iota only (no
    general sort, no gathers — see the closing paragraph), so it
    stays Mosaic-expressible; it is a PROTOTYPE gated behind
    ``merge_impl="bitonic"`` in ``mips_topk`` (differential-tested
    against the K-round merge, no default flip) pending a real-TPU
    profile.

    The sort's total order is strict on real entries (global ids are
    distinct), so the result is order-deterministic; equal
    ``(NEG_INF, ID_PAD)`` padding entries are interchangeable. Slots
    left at ``NEG_INF`` after the sort emit ``ID_PAD`` exactly like
    the K-round merge's exhausted-row rule.

    The lane-``xor``-``j`` partner exchange is a static
    reshape-flip-reshape (blocks of ``j`` lanes swapped pairwise), not
    a gather — the kernel captures no index constants and stays inside
    the max/min/where/iota/reshape vocabulary of the K-round merge.
    """
    cat_v = jnp.concatenate([vals, tile_vals], axis=-1)
    cat_i = jnp.concatenate([ids, tile_ids], axis=-1)
    w = cat_v.shape[-1]
    big = 1 << max(w - 1, 0).bit_length()  # next power of two ≥ w
    pad = big - w
    if pad:
        widths = [(0, 0)] * (cat_v.ndim - 1) + [(0, pad)]
        cat_v = jnp.pad(cat_v, widths, constant_values=NEG_INF)
        cat_i = jnp.pad(cat_i, widths, constant_values=ID_PAD)
    lead = cat_v.shape[:-1]

    def partner(a, j):
        # lane ^ j as a static permutation: swap adjacent j-blocks.
        a = a.reshape(lead + (big // (2 * j), 2, j))
        return jnp.flip(a, axis=-2).reshape(lead + (big,))

    lane = jax.lax.broadcasted_iota(jnp.int32, cat_v.shape, cat_v.ndim - 1)
    # Classic iterative bitonic network, directions inverted so the
    # final order is the merge's key order (value desc, id asc).
    size = 2
    while size <= big:
        j = size // 2
        while j >= 1:
            pv = partner(cat_v, j)
            pi = partner(cat_i, j)
            is_lower = (lane & j) == 0
            in_order_block = (lane & size) == 0
            want_first = is_lower == in_order_block
            mine_first = _precedes(cat_v, cat_i, pv, pi)
            keep_mine = mine_first == want_first
            cat_v = jnp.where(keep_mine, cat_v, pv)
            cat_i = jnp.where(keep_mine, cat_i, pi)
            j //= 2
        size *= 2
    v = cat_v[..., :k]
    i = cat_i[..., :k]
    return v, jnp.where(v == NEG_INF, ID_PAD, i)


def streaming_topk_elements(rows: int, k: int, block: int) -> int:
    """Analytic peak live elements of one streaming top-k pass: a
    ``(rows, block)`` score tile plus the ``(rows, k)`` value/id merge
    buffers — ``O(rows·(block + 2k))``, independent of the catalog size.
    The shared memory model behind ``eval.streaming.eval_peak_elements``
    and the fused-selection term of ``core.sce.sce_peak_elements``."""
    return rows * (block + 2 * k)
