"""Loss- and serve-side collectives for the vocab-parallel stack.

Two primitives cover every cross-``model``-shard exchange the SCE stack
performs (DESIGN.md §2/§4):

  * :func:`all_to_all_bucket_shuffle` — route per-bucket payloads to a
    contiguous owner shard; 1/m the payload of an all-gather. (Until
    PR 3 this carried exact-mode's (value, id, embedding-row) candidate
    triples; the ids-only exact mode now merges candidates through
    :func:`distributed_topk_from_local` instead — embeddings never
    cross the wire — and the shuffle is retained as a general
    bucket-routing primitive.)
  * :func:`distributed_topk` — exact two-stage top-k over a row-sharded
    score matrix: local top-k, one all-gather of the (m · k) candidate
    (value, global-id) pairs, local top-k over the union. The result is
    replicated over the axis, and ties resolve identically to a
    single-device ``lax.top_k`` (lower global id wins). Its merge stage
    is exposed as :func:`distributed_topk_from_local` for callers whose
    local candidates come from a streaming scorer rather than a dense
    local score matrix (``repro.eval``); the LSE sibling
    :func:`distributed_lse_from_local` merges per-shard online-
    logsumexp ``(m, s)`` carries the same way (shifted-sum psum/pmax —
    the fused eval kernel's NLL ridealong).

Both degrade to a single-device fallback when called outside
``shard_map`` (no axis bound) so the same step code runs on one device.

Payload accounting
------------------
Every collective records its modelled per-device wire bytes into a
trace-time log (shapes are static when the call is traced). The dry-run
(``launch/dryrun.py``) resets the log before lowering a cell and attaches
the captured records next to the HLO-parsed collective bytes, giving an
analytic cross-check of the wire model. Retracing (e.g. under
``jax.value_and_grad``) may record a call more than once; the log is a
model of what the *traced program text* contains, not an execution count.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
# The all-gather whose result is typed as replicated over the axis, so
# ``jax.shard_map``'s replication check accepts it with no extra
# collective. JAX exports it only from its private module.
from jax._src.lax.parallel import all_gather_invariant

_PAYLOAD_LOG: List[Dict[str, Any]] = []


def reset_payload_log() -> None:
    """Clear the trace-time collective payload log."""
    _PAYLOAD_LOG.clear()


def payload_log() -> List[Dict[str, Any]]:
    """Records appended since the last reset (most recent last)."""
    return list(_PAYLOAD_LOG)


def payload_summary() -> Dict[str, Any]:
    """Aggregate of the log in the same shape as dryrun's HLO report."""
    per_op: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for rec in _PAYLOAD_LOG:
        per_op[rec["op"]] = per_op.get(rec["op"], 0.0) + rec["wire_bytes"]
        counts[rec["op"]] = counts.get(rec["op"], 0) + 1
    return {
        "total_bytes": sum(per_op.values()),
        "per_op_bytes": per_op,
        "counts": counts,
    }


def _record(op: str, axis_name: str, shape, dtype, group: int) -> None:
    size = math.prod(shape) * jnp.dtype(dtype).itemsize
    # ring model, matching launch/dryrun.py: S·(g-1)/g over the wire
    wire = size * (group - 1) / max(group, 1)
    _PAYLOAD_LOG.append(
        {
            "op": op,
            "axis": axis_name,
            "shape": tuple(shape),
            "dtype": jnp.dtype(dtype).name,
            "payload_bytes": size,
            "wire_bytes": wire,
            "group_size": group,
        }
    )


def _axis_size(axis_name: str) -> Optional[int]:
    """Static size of a bound mesh axis, or None outside ``shard_map``."""
    try:
        return int(jax.lax.psum(1, axis_name))
    except NameError:  # unbound axis name — single-device fallback
        return None


def all_to_all_bucket_shuffle(x: jax.Array, axis_name: str) -> jax.Array:
    """Route per-bucket candidate payloads to their owning model shard.

    Payload is 1/m of the equivalent all-gather. Buckets are owned
    contiguously: shard ``j`` owns buckets ``[j·n_b/m, (j+1)·n_b/m)``.
    (Formerly the exact-mode candidate-triple carrier — DESIGN.md §4;
    retained as a general bucket-routing primitive since the ids-only
    rewrite.)

    Parameters
    ----------
    x : (n_b, ...) array
        This shard's payload for ALL ``n_b`` buckets — e.g. local top-k
        values ``(n_b, k)``, ids, or gathered embedding rows
        ``(n_b, k, d)``. ``n_b`` must divide the axis size ``m``.
    axis_name : str
        Mesh axis to shuffle over (``"model"`` in this stack).

    Returns
    -------
    (m, n_b/m, ...) array
        ``out[i]`` is shard ``i``'s payload for this shard's owned
        buckets. Differentiable (the transpose of an all_to_all is the
        inverse all_to_all), so exact-mode candidate embeddings carry
        gradients back to their home shard.

    Notes
    -----
    Single-device fallback (no bound axis): ``reshape`` to
    ``(1, n_b, ...)`` — the same rank/layout as the m=1 collective.
    """
    m = _axis_size(axis_name)
    if m is None:
        return x.reshape((1,) + x.shape)
    n_b = x.shape[0]
    assert n_b % m == 0, (n_b, m)
    xs = x.reshape((m, n_b // m) + x.shape[1:])
    _record("all-to-all", axis_name, xs.shape, x.dtype, m)
    return jax.lax.all_to_all(xs, axis_name, split_axis=0, concat_axis=0)


def distributed_topk_from_local(
    vals_l: jax.Array,
    gids_l: jax.Array,
    k: int,
    axis_name: str,
) -> Tuple[jax.Array, jax.Array]:
    """Merge per-shard top-k candidates into the exact global top-k —
    stage 2 of :func:`distributed_topk`, exposed for callers that
    produce their local candidates WITHOUT a dense local score matrix
    (e.g. ``repro.eval``'s streaming rank-and-topk, which only ever
    holds ``(B, k_local)`` accumulators per shard).

    Parameters
    ----------
    vals_l : (..., k_local) array
        This shard's local top candidates, sorted descending, value
        ties in ascending-global-id order (what ``lax.top_k`` and the
        streaming kernels both produce). Required for exact tie parity
        with a dense single-device ``lax.top_k``.
    gids_l : (..., k_local) int array
        Matching GLOBAL catalog ids.
    k : int
        Global candidates to keep; clamped to ``m · k_local``.
    axis_name : str
        Mesh axis the catalog is sharded over.

    Returns
    -------
    (values, global_ids) : each ``(..., min(k, m·k_local))``
        Replicated over ``axis_name`` (stage 2 runs identically on
        every shard). Candidates union in ascending shard order and
        ``lax.top_k`` breaks ties toward earlier positions ⇒ lower
        global id — the dense tie rule, provided shard ``i`` only owns
        ids below shard ``i+1``'s.

    Notes
    -----
    Single-device fallback (no bound axis): top-k over the given
    candidates as-is.
    """
    m = _axis_size(axis_name)
    k_local = vals_l.shape[-1]
    if m is None:
        kk = min(k, k_local)
        vals, sel = jax.lax.top_k(vals_l, kk)
        return vals, jnp.take_along_axis(gids_l, sel, axis=-1)

    _record("all-gather", axis_name, (m,) + vals_l.shape, vals_l.dtype, m)
    _record("all-gather", axis_name, (m,) + gids_l.shape, gids_l.dtype, m)
    # Invariant gathers: every shard merges the same union, so the
    # result is replicated over ``axis_name`` and typed so.
    vals_g = all_gather_invariant(vals_l, axis_name, axis=0)  # (m, ..., k_l)
    gids_g = all_gather_invariant(gids_l, axis_name, axis=0)

    union_shape = vals_l.shape[:-1] + (m * k_local,)
    vals_u = jnp.moveaxis(vals_g, 0, -2).reshape(union_shape)
    gids_u = jnp.moveaxis(gids_g, 0, -2).reshape(union_shape)

    kk = min(k, m * k_local)
    vals, sel = jax.lax.top_k(vals_u, kk)
    gids = jnp.take_along_axis(gids_u, sel, axis=-1)
    return vals, gids


def distributed_lse_from_local(
    m_l: jax.Array, s_l: jax.Array, axis_name: str
) -> jax.Array:
    """Merge per-shard online-logsumexp ``(m, s)`` carries into the
    exact global ``logsumexp`` — the standard shifted-sum combine, the
    LSE sibling of :func:`distributed_topk_from_local` for callers
    whose per-shard carry comes from a streaming scorer
    (``repro.eval``'s fused single-pass kernel) rather than a dense
    local score matrix.

    Parameters
    ----------
    m_l : (...,) f32
        This shard's running max over its local (masked) columns —
        ``NEG_INF``-valued rows (no valid local column) contribute
        nothing.
    s_l : (...,) f32
        This shard's running ``Σ exp(logit − m_l)`` over the same
        columns.
    axis_name : str
        Mesh axis the catalog/vocab columns are sharded over.

    Returns
    -------
    (...,) f32 ``logsumexp`` over the full (global) column set,
    replicated over ``axis_name``:
    ``M = pmax(m_l); M + log(psum(s_l · exp(m_l − M)))``. The shift
    keeps every ``exp`` argument ≤ 0, so shards with empty slices
    (``m_l = NEG_INF``) fold in as exact zeros.

    Notes
    -----
    Single-device fallback (no bound axis): ``m_l + log(s_l)``.
    """
    m = _axis_size(axis_name)
    if m is None:
        return m_l + jnp.log(s_l)
    _record("all-reduce", axis_name, m_l.shape, m_l.dtype, m)
    _record("all-reduce", axis_name, s_l.shape, s_l.dtype, m)
    m_g = jax.lax.pmax(m_l, axis_name)
    s_g = jax.lax.psum(s_l * jnp.exp(m_l - m_g), axis_name)
    return m_g + jnp.log(s_g)


def distributed_topk(
    scores: jax.Array, k: int, axis_name: str
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact global top-k over the last (``axis_name``-sharded) dim.

    Parameters
    ----------
    scores : (..., C_local) array
        Each shard's slice of a row-sharded score matrix whose global
        column ``c`` lives on shard ``c // C_local``.
    k : int
        Items to keep (clamped to the global column count).
    axis_name : str
        Mesh axis the columns are sharded over.

    Returns
    -------
    (values, global_ids, source_shard) : each ``(..., k)``
        Replicated over ``axis_name``.

    Notes
    -----
    Two stages: (1) local top-``min(k, C_local)``; (2) one all-gather
    of the ``(m · k_local)`` candidate (value, global-id) pairs and a
    local top-k over the union
    (:func:`distributed_topk_from_local`). Selection — including tie
    order — matches single-device ``lax.top_k`` on the concatenated
    scores: candidates union in ascending shard order and value ties
    break toward the lower global id, exactly the dense rule.

    Single-device fallback: plain ``lax.top_k`` with zero source shards.
    """
    c_local = scores.shape[-1]
    m = _axis_size(axis_name)
    if m is None:
        vals, idx = jax.lax.top_k(scores, min(k, c_local))
        return vals, idx, jnp.zeros_like(idx)

    k_local = min(k, c_local)
    shard = jax.lax.axis_index(axis_name)
    vals_l, idx_l = jax.lax.top_k(scores, k_local)
    gids_l = idx_l + shard * c_local

    vals, gids = distributed_topk_from_local(vals_l, gids_l, k, axis_name)
    return vals, gids, gids // c_local
