"""The streaming top-k merge (Mosaic-friendly, sort-free).

Every streaming top-k kernel — ``kernels/mips_topk.py`` (SCE candidate
selection and the serving scorer), ``kernels/eval_fused.py`` (the
single-pass evaluation scorer) and the deprecated
``kernels/eval_topk.py`` oracle — carries a sorted ``(rows, K)``
running buffer across catalog tiles and merges each tile's scores into
it with :func:`insert_topk_tile`. Mosaic has no general sort, so the
merge is rounds (a rolled loop) of *first-occurrence argmax* built from
max/min/where/iota and a one-lane shift: only the tile entries that
beat the buffer's k-th value are taken, in order, and inserted into
the sorted buffer; the round count is read from the data.

Tie rule (the load-bearing property): the running buffer is kept in
descending-value / ascending-id-within-ties order, tiles arrive in
ascending-global-id order, and an entry goes after the buffered values
equal to it — so among equal values the lowest global id wins, and by
induction over merges the final selection is *bit-identical to a
dense* ``lax.top_k``. ``dist.collectives.distributed_topk`` guarantees
the same rule, so dense, streaming, and sharded selections agree
exactly. ``kernels/ref.merge_topk_tile`` (``K`` argmax rounds over the
concatenation of buffer and tile) is the tests' oracle for it.

Exhausted rows (fewer than ``K`` valid columns seen so far) keep the
``ID_PAD`` placeholder instead of a duplicate real id, matching what
``lax.top_k`` leaves in the id-padded buffer slots.

Cost note: one pass over the tile finds the candidates, then each
inserted entry costs ``O(K + tile)`` VPU work, at most ``min(K, tile)``
entries per tile. Once the buffer holds good scores few tile entries
enter: on scores unrelated to the item id the rounds fall to a few
percent of ``K`` per tile. The gain rests on that property. A catalog
whose scores rise with the id is the worst case — every tile inserts
``min(K, tile)`` — and costs somewhat less than ``K`` argmax rounds
over the ``(K + tile)``-wide concatenation (0.83–0.88× of them on a
TPU v5e at ``K`` = 256 and 320, 512-wide tiles).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
ID_PAD = jnp.iinfo(jnp.int32).max


def insert_topk_tile(vals, ids, tile_vals, id0, k: int):
    """Merge one tile into the running top-k buffer by inserting, in
    order, only the tile entries that beat the buffer's k-th value —
    the same result as ``K`` first-occurrence-argmax rounds over the
    concatenation of buffer and tile, at a cost set by how many
    entries enter.

    ``kth`` is buffer slot ``k - 1`` (``NEG_INF`` until the buffer is
    full). Tile ids exceed every buffered id, so a tile score equal to
    ``kth`` loses the tie and only scores strictly above it are
    candidates. Each round takes the candidates' first-occurrence
    argmax ``m`` and inserts it after the buffered values ``>= m``
    (equal ones have lower ids), shifting the rest one lane right. As
    the buffer is sorted, those slots are a prefix, and the insertion
    slot is the first slot after it whose left neighbour is ``>= m``
    (or slot 0). A row whose ``m`` no longer beats the k-th value, or
    has no candidate left, changes no slot below ``k``. The entries
    that enter are a prefix of the candidates in (value desc, id asc)
    order and at most ``k`` of them do, so
    ``min(k, max over rows of #candidates)`` rounds — a data-dependent
    trip count — leave the first ``k`` slots exactly where
    ``ref.merge_topk_tile`` leaves them, ``ID_PAD`` slots included.

    Runs inside a Pallas TPU kernel: the lane shift is ``pltpu.roll``,
    a native rotate when ``w`` is a whole number of 128-lane vregs, so
    kernels keep their buffers at :func:`buffer_lanes` ``(k)`` lanes.

    Parameters
    ----------
    vals : (rows, w) f32 running top-k values, ``w >= k``, descending
        over the first ``k`` slots; ``NEG_INF`` in unfilled slots. Slots
        from ``k`` on hold what was shifted out and are never read back.
    ids : (rows, w) i32 matching ids; ``ID_PAD`` in unfilled slots.
    tile_vals : (rows, t) f32 masked tile scores.
    id0 : global id of the tile's first column; column ``c`` has id
        ``id0 + c``.
    k : top-k size (static).

    Returns
    -------
    (vals', ids') : the merged buffer, same invariants.
    """
    slot = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile_vals.shape, 1)
    width = tile_vals.shape[-1]
    kth = jnp.max(
        jnp.where(slot == k - 1, vals, NEG_INF), axis=-1, keepdims=True
    )
    cand = jnp.where(tile_vals > kth, tile_vals, NEG_INF)
    count = jnp.sum(
        (cand > NEG_INF).astype(jnp.int32), axis=-1, keepdims=True
    )
    n = jnp.minimum(jnp.max(count), k)
    slot0 = slot == 0

    def round_(_, carry):
        cand, vals, ids = carry
        m = jnp.max(cand, axis=-1, keepdims=True)
        first = jnp.min(
            jnp.where(cand == m, lane, width), axis=-1, keepdims=True
        )
        shifted = pltpu.roll(vals, 1, 1)
        keep = vals >= m
        put = jnp.logical_or(shifted >= m, slot0)
        vals = jnp.where(keep, vals, jnp.where(put, m, shifted))
        ids = jnp.where(
            keep, ids, jnp.where(put, id0 + first, pltpu.roll(ids, 1, 1))
        )
        return jnp.where(lane == first, NEG_INF, cand), vals, ids

    _, vals, ids = jax.lax.fori_loop(0, n, round_, (cand, vals, ids))
    return vals, ids


def buffer_lanes(k: int) -> int:
    """Lanes of a kernel's ``(rows, ·)`` merge buffer: ``k`` rounded up
    to whole 128-lane vregs."""
    return -(-k // 128) * 128


def streaming_topk_elements(rows: int, k: int, block: int) -> int:
    """Analytic peak live elements of one streaming top-k pass: a
    ``(rows, block)`` score tile plus the ``(rows, k)`` value/id merge
    buffers — ``O(rows·(block + 2k))``, independent of the catalog size.
    The shared memory model behind ``eval.streaming.eval_peak_elements``
    and the fused-selection term of ``core.sce.sce_peak_elements``."""
    return rows * (block + 2 * k)
