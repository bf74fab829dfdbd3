"""Manual-DMA fused candidate gather + in-bucket SCE — Pallas TPU.

``kernels/sce_bucket.py`` fused the bucket-logit tensor away but still
takes the gathered candidate embeddings ``y_b = Y[idx_y]`` as an HBM
input — a ``(n_b, b_y, d)`` tensor written by an XLA gather whose VJP
scatter-adds into a ``(C, d)`` zeros buffer every step. These variants
close that last materialization: the catalog table ``Y (C, d)`` stays in
HBM (``memory_space=pl.ANY``), the gather rows ``idx_y`` arrive by
scalar prefetch (SMEM), and each grid step DMAs its ``block_by``
candidate rows ``Y[idx_y[n, j]]`` straight into a ``(block_by, d)`` VMEM
tile with ``pltpu.make_async_copy`` — every copy of the tile is issued
before the first wait, so the row DMAs overlap each other.

On TPU a row copy must span whole 128-lane rows of the HBM tiling, so
the wrappers zero-pad ``d`` to a multiple of 128 for Mosaic (exact: the
padded lanes add 0 to every dot product) and slice the gradients back;
interpret mode copies the rows at their own width. A table wider than
128 lanes is tiled eight rows deep in HBM, where one row is no legal
slice, so it is viewed as ``(C, 1, d)`` — one row per tile — and the
row tiles in VMEM likewise (``_wide``).

Forward grid ``(n_b, n_bx, n_by)``: one gathered ``(block_by, d)`` tile
per step feeds one ``(block_bx × block_by)`` MXU matmul and the carried
online logsumexp — the ``sce_bucket`` recurrence. Per-position vectors
(targets, positives, lse, cotangents) travel as ``(n_b, b_x, 1)``
columns and candidate ids as ``(n_b, 1, b_y)`` rows, so every block
meets the TPU (8, 128) tiling rule.

Backward is ONE kernel on grid ``(n_b, n_by, n_bx)`` (``b_x``
innermost): each step recomputes the softmax tile once and feeds both
gradients. ``dX`` accumulates in a per-bucket ``(b_x, d)`` VMEM scratch
written out once per bucket; ``dY`` accumulates a ``(block_by, d)``
tile over the ``b_x`` tiles and is then added into the ``(C, d)``
output in HBM by a manual read-modify-write: DMA the rows
``dY[idx_y[n, j]]`` in, add, DMA them back, waiting on each batch. The
output is aliased to a zeros operand (``input_output_aliases``), so the
XLA scatter-add disappears. The RMW skips every slot whose candidate id
is negative (padding, and in the distributed exact mode candidates
owned by another catalog shard). A row that occurs in several live
slots of one tile gets the SUM of their gradients in each of those
slots (a 0/1 same-row matrix times the tile accumulator), and all reads
finish before the first write, so duplicate slots write identical
values. One step's writes complete before the next step's reads, so a
catalog row shared by adjacent buckets accumulates correctly.

Masking follows ``sce_bucket`` plus one rule: candidates with a
NEGATIVE id in ``cand_ids`` are invalid for *every* position — padding
slots, and (in the distributed ids-only exact mode) candidates owned by
another catalog shard, whose partial LSE is computed at home and merged
by psum.

Selection indices are non-differentiable; ``idx_y``/``tgt_b``/
``cand_ids`` get no cotangent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.linear_sce import _cap_deriv, _capped
from repro.kernels.common import dot, pad_to, psum_to, sds, vary_as

NEG_INF = -1e30
LANE = 128


def _wide(dp: int) -> bool:
    """Whether rows ``dp`` wide travel as ``(1, dp)`` tiles of a
    ``(rows, 1, dp)`` view: wider than one 128-lane tile row."""
    return dp > LANE


def _tile(ref):
    """The ``(rows, dp)`` value of a row-tile scratch of either layout."""
    return ref[:, 0, :] if len(ref.shape) == 3 else ref[...]


def _tile_mask(cand_row, tgt_col):
    """(block_bx, block_by) invalid mask: the position's own target, and
    negative candidate ids (padding / not owned by this shard)."""
    return jnp.logical_or(cand_row == tgt_col, cand_row < 0)


def _row_copies(rows, n, base, count, src, dst, sem, *, hbm_is_src,
                when=None):
    """Issue ``count`` single-row DMAs between HBM row ``rows[n, base+r]``
    and VMEM row ``r`` (direction per ``hbm_is_src``), then wait for all
    of them. ``when(r)`` optionally skips slots (same rule for the
    start and the wait, so the semaphore balances)."""

    def copy(r):
        hbm_row = src if hbm_is_src else dst
        vmem = dst if hbm_is_src else src
        h = hbm_row.at[pl.ds(rows[n, base + r], 1)]
        v = vmem.at[pl.ds(r, 1)]
        return pltpu.make_async_copy(h, v, sem) if hbm_is_src else (
            pltpu.make_async_copy(v, h, sem)
        )

    def each(fn):
        def body(r, carry):
            if when is None:
                fn(copy(r))
            else:
                pl.when(when(r))(lambda: fn(copy(r)))
            return carry

        jax.lax.fori_loop(0, count, body, 0)

    each(lambda c: c.start())
    each(lambda c: c.wait())


# ---------------------------------------------------------------------------
# Forward (loss and partial-LSE flavours share one body)
# ---------------------------------------------------------------------------
def _gfwd_kernel(
    idx_ref,  # (n_b, by_p) i32 scalar-prefetch — rows of Y to gather
    *refs,
    n_by_tiles: int,
    block_by: int,
    with_pos: bool,
    logit_softcap: float | None,
):
    if with_pos:
        (tgt_ref, cand_ref, pos_ref, x_ref, y_hbm,
         loss_ref, lse_ref, gather_scr, sem, m_scr, s_scr) = refs
    else:
        (tgt_ref, cand_ref, x_ref, y_hbm,
         lse_ref, gather_scr, sem, m_scr, s_scr) = refs
    n = pl.program_id(0)
    jt = pl.program_id(2)

    @pl.when(jt == 0)
    def _init():
        if with_pos:
            # Fold the positive into the accumulator (KERNELS.md
            # §sce_bucket): m = pos, s = exp(pos - pos) = 1.
            pos = pos_ref[0].astype(jnp.float32)
            m_scr[...] = pos
            s_scr[...] = jnp.ones_like(pos)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            s_scr[...] = jnp.zeros_like(s_scr)

    _row_copies(idx_ref, n, jt * block_by, block_by, y_hbm, gather_scr,
                sem, hbm_is_src=True)
    logits = dot(x_ref[0], _tile(gather_scr).T)
    # Softcap INSIDE the tile, before the invalid mask (CE is not
    # cap-invariant; cap(NEG_INF) would be −cap). The folded positive is
    # pre-capped by the caller, so the m = pos init is consistent.
    logits = _capped(logits, logit_softcap)
    logits = jnp.where(_tile_mask(cand_ref[0], tgt_ref[0]), NEG_INF, logits)
    m_prev, s_prev = m_scr[...], s_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    s_scr[...] = s_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=-1, keepdims=True
    )
    m_scr[...] = m_new

    @pl.when(jt == n_by_tiles - 1)
    def _finalize():
        m, s = m_scr[...], s_scr[...]
        if with_pos:
            lse = m + jnp.log(s)
            lse_ref[0] = lse.astype(lse_ref.dtype)
            loss_ref[0] = (lse - pos_ref[0].astype(jnp.float32)).astype(
                loss_ref.dtype
            )
        else:
            lse_ref[0] = (m + jnp.log(jnp.maximum(s, 1e-30))).astype(
                lse_ref.dtype
            )


# ---------------------------------------------------------------------------
# Backward — one softmax tile per step feeds dX (VMEM, per bucket) and
# dY (manual RMW into the gather-indexed rows of the (C, d) output)
# ---------------------------------------------------------------------------
def _gbwd_kernel(
    idx_ref,  # (n_b, by_p) i32 scalar-prefetch — catalog rows
    cand_sm,  # (n_b, by_p) i32 scalar-prefetch — RMW slot validity
    tgt_ref,  # (1, bx_t, 1) i32
    cand_ref,  # (1, 1, by_t) i32
    rowid_ref,  # (1, by_t, 1) i32 — idx_y as a column (same-row matrix)
    rowid_t_ref,  # (1, 1, by_t) i32 — idx_y as a row
    lse_ref,  # (1, bx_t, 1) f32
    g_ref,  # (1, bx_t, 1) upstream cotangent
    x_ref,  # (1, bx_t, d)
    y_hbm,  # (C, d) in HBM ((C, 1, d) when wide, as the three below)
    dyz_hbm,  # (C, d) zeros, aliased to dy_hbm
    dx_ref,  # (1, bx_p, d) out — written once per bucket
    dy_hbm,  # (C, d) out in HBM
    gather_scr,  # (by_t, d) y dtype
    rmw_scr,  # (by_t, d) dy dtype
    sem,
    dx_acc,  # (bx_p, d) f32
    dy_acc,  # (by_t, d) f32
    *,
    n_by_tiles: int,
    n_bx_tiles: int,
    block_bx: int,
    block_by: int,
    logit_softcap: float | None,
):
    del dyz_hbm  # present only to pin the zeros aliasing
    n = pl.program_id(0)
    jt = pl.program_id(1)
    ix = pl.program_id(2)
    base = jt * block_by

    @pl.when(jnp.logical_and(jt == 0, ix == 0))
    def _init_dx():
        dx_acc[...] = jnp.zeros_like(dx_acc)

    @pl.when(ix == 0)
    def _gather():
        _row_copies(idx_ref, n, base, block_by, y_hbm, gather_scr, sem,
                    hbm_is_src=True)
        dy_acc[...] = jnp.zeros_like(dy_acc)

    x = x_ref[0]
    tile = _tile(gather_scr)
    logits = dot(x, tile.T)
    capped = _capped(logits, logit_softcap)
    invalid = _tile_mask(cand_ref[0], tgt_ref[0])
    p = jnp.where(invalid, 0.0, jnp.exp(capped - lse_ref[0]))
    gw = p * _cap_deriv(capped, logit_softcap)
    gw = gw * g_ref[0].astype(jnp.float32)
    rows = pl.ds(pl.multiple_of(ix * block_bx, block_bx), block_bx)
    dx_acc[rows, :] += dot(gw.astype(tile.dtype), tile)
    dy_acc[...] += dot(gw.T.astype(x.dtype), x)

    @pl.when(ix == n_bx_tiles - 1)
    def _flush_dy():
        # Read-modify-write of this tile's catalog rows. Slots with a
        # negative candidate id carry no gradient and may repeat a row,
        # so they are skipped; the rest are distinct rows.
        def owned(r):
            return cand_sm[n, base + r] >= 0

        same_row = jnp.logical_and(
            rowid_ref[0] == rowid_t_ref[0], cand_ref[0] >= 0
        ).astype(jnp.float32)
        summed = dot(same_row, dy_acc[...])
        _row_copies(idx_ref, n, base, block_by, dy_hbm, rmw_scr, sem,
                    hbm_is_src=True, when=owned)
        if len(rmw_scr.shape) == 3:
            rmw_scr[:, 0, :] += summed.astype(rmw_scr.dtype)
        else:
            rmw_scr[...] += summed.astype(rmw_scr.dtype)
        _row_copies(idx_ref, n, base, block_by, rmw_scr, dy_hbm, sem,
                    hbm_is_src=False, when=owned)

    @pl.when(jnp.logical_and(jt == n_by_tiles - 1, ix == n_bx_tiles - 1))
    def _write_dx():
        dx_ref[0] = dx_acc[...].astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------
def _prep(x_b, y, idx_y, tgt_b, cand_ids, block_bx, block_by, interpret):
    n_b, b_x, d = x_b.shape
    b_y = idx_y.shape[1]
    c = y.shape[0]
    block_bx = min(block_bx, b_x)
    block_by = min(block_by, b_y)

    # Mosaic: lane-pad d so each row DMA spans whole 128-lane rows.
    lane = 1 if interpret else LANE
    xp = pad_to(pad_to(x_b, 1, block_bx), 2, lane)
    yp = pad_to(y, 1, lane)
    if _wide(yp.shape[1]):
        yp = yp.reshape(c, 1, yp.shape[1])
    tp = pad_to(tgt_b[..., None], 1, block_bx, value=-2)
    # Padded gather slots repeat the bucket's LAST real row (edge pad):
    # an in-range row for the masked forward. The cand-id pad of -1
    # masks the slots and keeps them out of the dY RMW.
    pad_by = (-idx_y.shape[1]) % max(block_by, 1)
    ip = jnp.clip(
        jnp.pad(idx_y, ((0, 0), (0, pad_by)), mode="edge"), 0, c - 1
    ).astype(jnp.int32)
    cp = pad_to(cand_ids, 1, block_by, value=-1).astype(jnp.int32)
    bx_p, by_p = xp.shape[1], ip.shape[1]
    return (
        xp, yp, tp, ip, cp,
        dict(
            n_b=n_b, b_x=b_x, d=d, block_bx=block_bx, block_by=block_by,
            bx_p=bx_p, n_bx=bx_p // block_bx, n_by=by_p // block_by,
        ),
    )


def _col_spec(block_bx, order):
    """(1, block_bx, 1) per-position column block; ``order`` names the
    grid axes as ``"nij"`` (forward) or ``"nji"`` (backward)."""
    if order == "nij":
        return pl.BlockSpec((1, block_bx, 1), lambda n, i, j, *_: (n, i, 0))
    return pl.BlockSpec((1, block_bx, 1), lambda n, j, i, *_: (n, i, 0))


def _gfwd(x_b, y, idx_y, tgt_b, cand_ids, pos_logit, *, block_bx, block_by,
          interpret, with_pos, logit_softcap=None):
    xp, yp, tp, ip, cp, s = _prep(
        x_b, y, idx_y, tgt_b, cand_ids, block_bx, block_by, interpret
    )
    block_bx, block_by = s["block_bx"], s["block_by"]
    dp = xp.shape[-1]
    row_tile = (block_by,) + yp.shape[1:]

    kernel = functools.partial(
        _gfwd_kernel,
        n_by_tiles=s["n_by"],
        block_by=block_by,
        with_pos=with_pos,
        logit_softcap=logit_softcap,
    )
    col = _col_spec(block_bx, "nij")
    in_specs = [
        col,  # tgt
        pl.BlockSpec((1, 1, block_by), lambda n, i, j, idx: (n, 0, j)),
    ]
    inputs = [tp, cp[:, None, :]]
    if with_pos:
        in_specs.append(col)
        inputs.append(pad_to(pos_logit[..., None], 1, block_bx))
    in_specs += [
        pl.BlockSpec((1, block_bx, dp), lambda n, i, j, idx: (n, i, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # catalog table stays in HBM
    ]
    inputs += [xp, yp]

    out_dtypes = ([pos_logit.dtype] if with_pos else []) + [jnp.float32]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s["n_b"], s["n_bx"], s["n_by"]),
            in_specs=in_specs,
            out_specs=[col] * len(out_dtypes),
            scratch_shapes=[
                pltpu.VMEM(row_tile, y.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.VMEM((block_bx, 1), jnp.float32),
                pltpu.VMEM((block_bx, 1), jnp.float32),
            ],
        ),
        out_shape=[
            sds((s["n_b"], s["bx_p"], 1), dt, ip, *inputs)
            for dt in out_dtypes
        ],
        interpret=interpret,
        name="sce_gather_fwd",
    )(ip, *inputs)
    out = [o[:, : s["b_x"], 0] for o in out]
    return tuple(out) if with_pos else out[0]


def _gbwd(x_b, y, idx_y, tgt_b, cand_ids, lse, g, *, block_bx, block_by,
          interpret, logit_softcap=None):
    xp, yp, tp, ip, cp, s = _prep(
        x_b, y, idx_y, tgt_b, cand_ids, block_bx, block_by, interpret
    )
    block_bx, block_by = s["block_bx"], s["block_by"]
    d, dp, bx_p = s["d"], xp.shape[-1], s["bx_p"]
    row_tile = (block_by,) + yp.shape[1:]
    lp = pad_to(lse[..., None], 1, block_bx)
    gp = pad_to(g[..., None], 1, block_bx)  # zero cotangent on padded rows
    col = _col_spec(block_bx, "nji")
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = (tp, cp[:, None, :], ip[:, :, None], ip[:, None, :], lp, gp,
                xp, yp)
    operands += (vary_as(jnp.zeros_like(yp), *operands),)
    by_row = pl.BlockSpec(
        (1, 1, block_by), lambda n, j, i, idx, cand: (n, 0, j)
    )

    dx, dy = pl.pallas_call(
        functools.partial(
            _gbwd_kernel,
            n_by_tiles=s["n_by"],
            n_bx_tiles=s["n_bx"],
            block_bx=block_bx,
            block_by=block_by,
            logit_softcap=logit_softcap,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # idx_y (DMA rows) + cand_ids (RMW mask)
            grid=(s["n_b"], s["n_by"], s["n_bx"]),
            in_specs=[
                col,
                by_row,
                pl.BlockSpec(
                    (1, block_by, 1), lambda n, j, i, idx, cand: (n, j, 0)
                ),
                by_row,
                col,
                col,
                pl.BlockSpec(
                    (1, block_bx, dp), lambda n, j, i, idx, cand: (n, i, 0)
                ),
                hbm,
                hbm,
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, bx_p, dp), lambda n, j, i, idx, cand: (n, 0, 0)
                ),
                hbm,
            ],
            scratch_shapes=[
                pltpu.VMEM(row_tile, y.dtype),
                pltpu.VMEM(row_tile, y.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.VMEM((bx_p, dp), jnp.float32),
                pltpu.VMEM((block_by, dp), jnp.float32),
            ],
        ),
        out_shape=[
            sds((s["n_b"], bx_p, dp), x_b.dtype, *operands),
            sds(yp.shape, y.dtype, *operands),
        ],
        # operand 10 = the zeros (after 2 prefetch args and 8 inputs).
        input_output_aliases={10: 1},
        interpret=interpret,
        name="sce_gather_bwd",
    )(ip, cp, *operands)

    return dx[:, : s["b_x"], :d], dy.reshape(dy.shape[0], dp)[:, :d]


# ---------------------------------------------------------------------------
# Public ops with custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def sce_gather_loss(
    x_b,
    y,
    idx_y,
    tgt_b,
    cand_ids,
    pos_logit,
    block_bx: int = 128,
    block_by: int = 256,
    interpret: bool = False,
    logit_softcap: float | None = None,
):
    """Fused in-bucket SCE losses with on-the-fly candidate gather:
    ``(n_b, b_x)`` per-(bucket, position) CE from ``x_b`` and the FULL
    catalog ``y (C, d)`` + gather rows ``idx_y (n_b, b_y)``. Matches
    ``ref.sce_bucket_loss_ref(x_b, y[idx_y], tgt_b, cand_ids, pos)``;
    the ``(n_b, b_y, d)`` candidate tensor never exists, and ``dY``
    lands directly in a ``(C, d)`` buffer (no gather-VJP scatter).
    ``logit_softcap`` caps every negative logit INSIDE the tile;
    ``pos_logit`` must arrive already capped (its tanh derivative flows
    through the caller's autodiff via the ``d_pos`` cotangent)."""
    loss, _ = _gfwd(
        x_b, y, idx_y, tgt_b, cand_ids, pos_logit,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        with_pos=True, logit_softcap=logit_softcap,
    )
    return loss


def _loss_vjp_fwd(x_b, y, idx_y, tgt_b, cand_ids, pos_logit, block_bx,
                  block_by, interpret, logit_softcap):
    loss, lse = _gfwd(
        x_b, y, idx_y, tgt_b, cand_ids, pos_logit,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        with_pos=True, logit_softcap=logit_softcap,
    )
    return loss, (x_b, y, idx_y, tgt_b, cand_ids, pos_logit, lse)


def _loss_vjp_bwd(block_bx, block_by, interpret, logit_softcap, res, g):
    x_b, y, idx_y, tgt_b, cand_ids, pos_logit, lse = res
    dx, dy = _gbwd(
        x_b, y, idx_y, tgt_b, cand_ids, lse, g,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        logit_softcap=logit_softcap,
    )
    p_pos = jnp.exp(pos_logit.astype(jnp.float32) - lse)
    d_pos = ((p_pos - 1.0) * g.astype(jnp.float32)).astype(pos_logit.dtype)
    return (psum_to(dx, x_b), psum_to(dy, y), None, None, None,
            psum_to(d_pos, pos_logit))


sce_gather_loss.defvjp(_loss_vjp_fwd, _loss_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def sce_gather_plse(
    x_b,
    y,
    idx_y,
    tgt_b,
    cand_ids,
    block_bx: int = 128,
    block_by: int = 256,
    interpret: bool = False,
    logit_softcap: float | None = None,
):
    """Partial in-bucket logsumexp with on-the-fly candidate gather —
    ``(n_b, b_x)`` f32, the distributed-merge building block. Matches
    ``ref.sce_bucket_plse_ref(x_b, y[idx_y], tgt_b, cand_ids)`` with
    negative ``cand_ids`` masked (padding / other-shard-owned);
    ``logit_softcap`` caps inside the tile."""
    return _gfwd(
        x_b, y, idx_y, tgt_b, cand_ids, None,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        with_pos=False, logit_softcap=logit_softcap,
    )


def _plse_vjp_fwd(x_b, y, idx_y, tgt_b, cand_ids, block_bx, block_by,
                  interpret, logit_softcap):
    lse = _gfwd(
        x_b, y, idx_y, tgt_b, cand_ids, None,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        with_pos=False, logit_softcap=logit_softcap,
    )
    return lse, (x_b, y, idx_y, tgt_b, cand_ids, lse)


def _plse_vjp_bwd(block_bx, block_by, interpret, logit_softcap, res, g):
    x_b, y, idx_y, tgt_b, cand_ids, lse = res
    dx, dy = _gbwd(
        x_b, y, idx_y, tgt_b, cand_ids, lse, g,
        block_bx=block_bx, block_by=block_by, interpret=interpret,
        logit_softcap=logit_softcap,
    )
    return psum_to(dx, x_b), psum_to(dy, y), None, None, None


sce_gather_plse.defvjp(_plse_vjp_fwd, _plse_vjp_bwd)
