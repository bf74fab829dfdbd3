"""Leave-one-out streaming evaluation driver (DESIGN.md §Eval).

Replaces ``core.metrics.evaluate_seqrec`` as the production eval path:
same leave-one-out protocol, same unsampled metrics, but scored through
``repro.eval.streaming`` so no ``(B, C)`` score matrix ever exists —
``core.metrics`` stays as the dense oracle the tests compare against.

Model-agnosticism is a ``score_fn`` protocol::

    score_fn(params, tokens) -> (states, catalog)

where ``tokens`` are the kept right-aligned eval sequences (the held-out
target still in the last column), ``states`` is the ``(B, d)`` user
representation at the scoring position and ``catalog`` the shard-even
``(C_pad, d)`` item table slice (``loss_catalog`` — phantom rows are
masked by id range, so eval shards the catalog exactly like the loss
does). ``sasrec_score_fn`` hides the target and re-right-aligns;
``bert4rec_score_fn`` replaces it with [MASK] (the Cloze eval protocol);
``lm_score_fn`` flattens EVERY next-token position into an eval row
(``(B·T, d)`` states against the padded vocab table — the token-rank
protocol, driven by :func:`evaluate_streaming_lm`).

Sharded path: with a ``mesh``, scoring runs under ``shard_map`` — batch
rows over the data axes, catalog rows over ``model``
(``dist.sharding.catalog_spec``) — each model shard runs ONE fused
streaming sweep over its slice (chunked reference; interpret-mode
Pallas cannot run under shard_map, see ``kernels/ops.py``) after a
cheap psum'd ``eval_tgt_gather`` pre-stage supplies the full-catalog
target score; rank counts ``psum`` across ``model``, per-shard top-k
candidates merge through
``dist.collectives.distributed_topk_from_local``, and the LM NLL's
per-shard online-LSE carries merge through
``dist.collectives.distributed_lse_from_local`` (shifted-sum
psum/pmax). Per-device peak stays ``O(B_local·(K + block))``.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.collectives import (
    distributed_lse_from_local,
    distributed_topk_from_local,
)
from repro.dist.sharding import batch_spec, catalog_spec, data_axes
from repro.eval.streaming import (
    MetricAccumulator,
    TokenRankAccumulator,
    ranks_from_counts,
    streaming_eval_scores,
)
from repro.kernels import ops

ScoreFn = Callable[..., Tuple[jax.Array, jax.Array]]


# ---------------------------------------------------------------------------
# score_fn implementations
# ---------------------------------------------------------------------------
def sasrec_score_fn(cfg) -> ScoreFn:
    """Causal leave-one-out: hide the last real item, re-right-align,
    encode, take the last position's hidden state."""
    from repro.models import sasrec

    def fn(params, tokens):
        last = tokens.shape[1] - 1
        prefix = tokens.at[:, last].set(0)
        prefix = jnp.roll(prefix, 1, axis=1)  # keep right alignment
        prefix = prefix.at[:, 0].set(0)
        hidden = sasrec.forward(params, cfg, prefix)
        return hidden[:, -1], sasrec.loss_catalog(params, cfg)

    return fn


def bert4rec_score_fn(cfg) -> ScoreFn:
    """Cloze leave-one-out: replace the held-out item with [MASK] and
    score that position (Sun et al. 2019 eval protocol)."""
    from repro.models import bert4rec as b4r
    from repro.models import sasrec

    def fn(params, tokens):
        last = tokens.shape[1] - 1
        masked = tokens.at[:, last].set(b4r.mask_token_id(cfg))
        hidden = b4r.forward(params, cfg, masked)
        return hidden[:, -1], sasrec.loss_catalog(params, cfg)

    return fn


def default_score_fn(cfg) -> ScoreFn:
    """SASRec for causal configs, BERT4Rec otherwise."""
    return sasrec_score_fn(cfg) if cfg.causal else bert4rec_score_fn(cfg)


def lm_score_fn(cfg) -> ScoreFn:
    """Next-token protocol for the transformer LM family: ONE forward
    over the ``(B, T)`` token batch, then every position becomes an
    eval row — hidden states flatten ``(B, T, d) → (B·T, d)`` and score
    against the full (padded) output embedding ``(V_pad, d)``. Which
    rows actually count (padding positions, the final position, rows
    whose next token is the pad id) is decided by the validity mask
    (:func:`lm_targets_and_valid`) AFTER the streamed scoring, so the
    scorer keeps a static shape.

    Note on gemma-2's final-logit softcap: ``cap·tanh(·/cap)`` is
    strictly monotone, so ranks, top-k ids and tie order are invariant
    under it — token-rank metrics are computed from the raw streamed
    scores. (The reported next-token ``loss`` is NOT cap-invariant and
    applies the cap inside its chunked scan; see
    :func:`evaluate_streaming_lm`.)
    """
    from repro.models import transformer as tf_lib

    def fn(params, tokens):
        hidden, _ = tf_lib.forward(params, cfg, tokens)
        states = hidden.reshape(-1, hidden.shape[-1])
        return states, tf_lib.output_embedding(params, cfg)

    return fn


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def _keep_and_targets(tokens: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Filter sequences with ≥ 2 real items; the held-out target is the
    last (right-aligned) position."""
    lengths = (tokens != 0).sum(axis=1)
    kept = tokens[lengths >= 2]
    b, l = kept.shape
    targets = kept[np.arange(b), l - 1].copy()
    return kept, targets


def evaluate_streaming(
    params,
    cfg,
    eval_batch,
    *,
    ks: Sequence[int] = (1, 5, 10),
    score_fn: Optional[ScoreFn] = None,
    mesh=None,
    block_b: int = 128,
    block_c: int = 512,
    impl: str = "auto",
    interpret: bool | None = None,
    accumulator: Optional[MetricAccumulator] = None,
) -> Dict[str, float]:
    """Leave-one-out evaluation without materializing ``(B, C)`` scores.

    Parameters
    ----------
    params, cfg : model params + ``SeqRecConfig``.
    eval_batch : dict with right-aligned ``"tokens"`` (B, L).
    ks : metric cutoffs.
    score_fn : the model protocol (default: by ``cfg.causal``).
    mesh : optional — run the scoring under ``shard_map`` with the
        catalog sharded over ``model`` and batch rows over the data
        axes. The sharded path always streams through the chunked
        reference (interpret-mode Pallas cannot run under shard_map —
        see ``kernels/ops.py``), so ``impl``, ``interpret`` and
        ``block_b`` apply to the single-device path only; ``block_c``
        applies to both.
    impl, interpret, block_b, block_c : scorer knobs
        (see ``streaming_rank_topk``).
    accumulator : fold into an existing ``MetricAccumulator`` (multi-
        batch evaluation); a fresh one is used otherwise.

    Returns
    -------
    dict — same keys (``hr@k`` / ``ndcg@k`` / ``cov@k``) and, on a
    single batch, the same values as the ``core.metrics.topk_metrics``
    oracle.

    Profiler spans: ``repro.eval.encode`` (the encoder),
    ``repro.eval.score`` (the catalog sweep) and ``repro.eval.fold``
    (readback and the host metric fold).
    """
    if score_fn is None:
        score_fn = default_score_fn(cfg)
    tokens, targets = _keep_and_targets(np.asarray(eval_batch["tokens"]))
    k = max(ks)

    if mesh is None:
        with jax.profiler.TraceAnnotation("repro.eval.encode"):
            states, catalog = score_fn(params, jnp.asarray(tokens))
        with jax.profiler.TraceAnnotation("repro.eval.score"):
            vals, ids, gt, eq, _tgt, _m, _s = streaming_eval_scores(
                states, catalog, jnp.asarray(targets), k,
                block_b=block_b, block_c=block_c,
                c_lo=1, c_hi=cfg.n_items,
                impl=impl, interpret=interpret,
            )
    else:
        vals, ids, gt, eq = _evaluate_sharded(
            params, cfg, tokens, targets, k,
            score_fn=score_fn, mesh=mesh, block_c=block_c,
        )

    with jax.profiler.TraceAnnotation("repro.eval.fold"):
        acc = accumulator or MetricAccumulator(ks, cfg.n_items)
        acc.update(ranks_from_counts(gt, eq), np.asarray(ids))
        return acc.result()


# jitted sharded scorers, keyed on everything the closure bakes in —
# periodic in-loop eval must NOT retrace/recompile every interval
_SHARDED_FNS: Dict[tuple, Callable] = {}


def _sharded_eval_fn(
    mesh, k, block_c, c_lo, c_hi, with_lse, logit_softcap
):
    cache_key = (mesh, k, block_c, c_lo, c_hi, with_lse, logit_softcap)
    fn = _SHARDED_FNS.get(cache_key)
    if fn is not None:
        return fn

    def inner(x_l, y_l, t_l):
        c_local = y_l.shape[0]
        offset = jax.lax.axis_index("model") * c_local
        # Target score from the shard that owns the row (others add 0)
        # — the cheap tile-shaped gather matmul, NOT a catalog sweep,
        # psum'd BEFORE the sweep so every shard compares its local
        # columns against the full-catalog target score.
        tgt = jax.lax.psum(
            ops.eval_tgt_gather(
                x_l, y_l, t_l, block_c=block_c, id_offset=offset
            ),
            "model",
        )
        vals_l, ids_l, gt_l, eq_l, _t, m_l, s_l = ops.eval_fused(
            x_l, y_l, t_l, k,
            tgt_scores=tgt, block_c=block_c, c_lo=c_lo, c_hi=c_hi,
            id_offset=offset, logit_softcap=logit_softcap,
            with_lse=with_lse,
        )
        gt = jax.lax.psum(gt_l, "model")
        eq = jax.lax.psum(eq_l, "model")
        vals, gids = distributed_topk_from_local(vals_l, ids_l, k, "model")
        if with_lse:
            lse = distributed_lse_from_local(m_l, s_l, "model")
            return vals, gids, gt, eq, tgt, lse
        return vals, gids, gt, eq, tgt

    n_row_outs = 4 if with_lse else 3  # gt, eq, tgt (+ lse)
    fn = jax.jit(jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            batch_spec(mesh, 2),
            catalog_spec(mesh),
            batch_spec(mesh, 1),
        ),
        out_specs=(
            batch_spec(mesh, 2),
            batch_spec(mesh, 2),
        ) + (batch_spec(mesh, 1),) * n_row_outs,
    ))
    _SHARDED_FNS[cache_key] = fn
    return fn


def _rank_topk_sharded(
    states, catalog, targets, k, *, mesh, block_c, c_lo, c_hi,
    with_lse=False, logit_softcap=None,
):
    """shard_map fused scoring over precomputed eval rows: ONE
    per-model-shard catalog sweep (after the cheap psum'd
    ``eval_tgt_gather`` pre-stage), psum'd rank counts, two-stage top-k
    merge, and — with ``with_lse`` — the shifted-sum psum/pmax LSE
    merge (``distributed_lse_from_local``) that replaces the old
    replicated ``ce_chunked`` V-sweep. Rows are padded to the data-axis
    product by repeating the last row (dropped after scoring).

    Returns ``(vals, ids, gt, eq, tgt)`` — plus ``lse`` when
    ``with_lse``."""
    dp = math.prod(mesh.shape[ax] for ax in data_axes(mesh)) or 1
    b = states.shape[0]
    pad = (-b) % dp
    if pad:
        states = jnp.concatenate([states, jnp.repeat(states[-1:], pad, 0)])
        targets = np.concatenate(
            [np.asarray(targets), np.asarray(targets)[-1:].repeat(pad, 0)]
        )

    fn = _sharded_eval_fn(
        mesh, k, block_c, c_lo, c_hi, with_lse, logit_softcap
    )
    with jax.set_mesh(mesh):
        outs = fn(states, catalog, jnp.asarray(targets, jnp.int32))
    if pad:
        outs = tuple(o[:b] for o in outs)
    return outs


def _evaluate_sharded(
    params, cfg, tokens, targets, k, *, score_fn, mesh, block_c
):
    """Leave-one-out sharded scoring: one eval row per kept sequence."""
    with jax.profiler.TraceAnnotation("repro.eval.encode"):
        states, catalog = score_fn(params, jnp.asarray(tokens))
    with jax.profiler.TraceAnnotation("repro.eval.score"):
        vals, ids, gt, eq, _tgt = _rank_topk_sharded(
            states, catalog, targets, k,
            mesh=mesh, block_c=block_c, c_lo=1, c_hi=cfg.n_items,
        )
    return vals, ids, gt, eq


# ---------------------------------------------------------------------------
# Held-out token-rank protocol (LM family)
# ---------------------------------------------------------------------------
def lm_targets_and_valid(
    tokens: np.ndarray, pad_id: int = 0
) -> Tuple[np.ndarray, np.ndarray]:
    """Next-token targets + validity mask for a ``(B, T)`` token batch.

    ``targets[i, t] = tokens[i, t+1]``; a position is valid iff it is a
    real (non-pad) token AND its next token is real — the final column
    and padding never count. Same convention as
    ``data.sequences.SequenceDataset.next_batch``.
    """
    tokens = np.asarray(tokens)
    targets = np.zeros_like(tokens)
    targets[:, :-1] = tokens[:, 1:]
    valid = tokens != pad_id
    valid[:, -1] = False
    valid &= targets != pad_id
    return targets, valid


def evaluate_streaming_lm(
    params,
    cfg,
    eval_batch,
    *,
    ks: Sequence[int] = (1, 5, 10),
    mesh=None,
    block_b: int = 128,
    block_c: int = 512,
    impl: str = "auto",
    interpret: bool | None = None,
    accumulator: Optional[TokenRankAccumulator] = None,
) -> Dict[str, float]:
    """Held-out token-rank evaluation of a transformer LM — every
    next-token position is scored against the full vocabulary without
    ever materializing the ``(B·T, V)`` logit matrix.

    The LM twin of :func:`evaluate_streaming`: one
    ``transformer.forward`` pass produces ``(B·T, d)`` eval rows
    (:func:`lm_score_fn`); ONE fused catalog sweep
    (``streaming_eval_scores`` with the online-LSE carry on) yields
    each position's target-token rank (pessimistic ties, ``c_lo=1`` /
    ``c_hi=cfg.vocab`` masking the pad id and the phantom padded vocab
    rows — a rank-only ``k=1`` pass, since no token-rank metric needs
    recommended ids) AND its next-token NLL: ``lse − softcap(tgt)``
    over the real vocabulary excluding the pad id, peak
    ``B·T·block_c`` elements, never ``B·T·V``. The pre-PR-5 stack
    streamed the vocab matmul three times here (target pass + rank
    pass + a separate ``ce_chunked`` scan); the fused sweep streams it
    once. Padding / final positions are dropped by the validity mask
    before folding into the :class:`TokenRankAccumulator`.
    gemma-2-style final-logit softcaps are monotone and therefore
    rank-invariant (ranks use raw logits), but CE is not: the cap is
    applied to the LSE carry inside the streamed tile, so the reported
    loss is the model's actual next-token NLL.

    Parameters
    ----------
    params, cfg : transformer params + ``TransformerConfig``.
    eval_batch : dict with ``"tokens"`` (B, T); the pipeline's
        ``"targets"`` / ``"valid"`` are consumed when present (they
        honor the dataset's pad id), else recomputed via
        :func:`lm_targets_and_valid`.
    ks : metric cutoffs.
    mesh : optional — shard the vocab table over ``model``
        (``catalog_spec``, the same vocab-parallel layout the SCE loss
        uses) and the ``B·T`` rows over the data axes; per-shard
        candidates merge through ``distributed_topk_from_local``.
    impl, interpret, block_b, block_c : scorer knobs
        (see ``streaming_rank_topk``; sharded path: ``block_c`` only).
    accumulator : fold into an existing :class:`TokenRankAccumulator`
        (multi-batch held-out streams); a fresh one otherwise.

    Returns
    -------
    dict — ``hr@k`` / ``ndcg@k`` / ``mean_rank`` / ``loss`` /
    ``n_tokens`` (see ``TokenRankAccumulator.result``). The sharded
    ``loss`` merges per-shard LSE carries exactly (shifted-sum
    psum/pmax); it can differ from the single-device fold order by f32
    rounding only.
    """
    from repro.core.sce import apply_softcap

    tokens = np.asarray(eval_batch["tokens"])
    if "targets" in eval_batch and "valid" in eval_batch:
        # the data pipeline already computed the next-token shift with
        # ITS pad id (SequenceDataset.next_batch) — consume it
        targets = np.asarray(eval_batch["targets"])
        valid = np.asarray(eval_batch["valid"])
    else:
        targets, valid = lm_targets_and_valid(tokens)
    t_flat = jnp.asarray(targets.reshape(-1), jnp.int32)
    v_flat = valid.reshape(-1)

    # Every token-rank metric is a function of the rank counts alone
    # (TokenRankAccumulator folds no ids — there is no COV here), so
    # the fused sweep runs with k=1: the top-k merge recurrence costs
    # one round per tile, discarded beyond the counts. The same sweep
    # carries the online-LSE NLL accumulator — the columns it masks
    # ([1, V): no pad id, no phantom rows) are exactly the NLL's
    # candidate set, so rank pass and loss pass collapse into one.
    cap = getattr(cfg, "final_softcap", None)
    states, catalog = lm_score_fn(cfg)(params, jnp.asarray(tokens))
    if mesh is None:
        _, _, gt, eq, tgt, m, s = streaming_eval_scores(
            states, catalog, t_flat, 1,
            block_b=block_b, block_c=block_c,
            c_lo=1, c_hi=cfg.vocab,
            impl=impl, interpret=interpret,
            with_lse=True, logit_softcap=cap,
        )
        lse = jnp.asarray(m) + jnp.log(jnp.asarray(s))
    else:
        _, _, gt, eq, tgt, lse = _rank_topk_sharded(
            states, catalog, t_flat, 1,
            mesh=mesh, block_c=block_c, c_lo=1, c_hi=cfg.vocab,
            with_lse=True, logit_softcap=cap,
        )
    ranks = ranks_from_counts(gt, eq)[v_flat]

    # Next-token NLL from the sweep's own carries: lse − softcap(tgt).
    # Invalid rows (pad targets) carry a garbage tgt — they are
    # dropped by the validity mask before the fold, never reported.
    nll = np.asarray(lse) - np.asarray(apply_softcap(jnp.asarray(tgt), cap))
    acc = accumulator or TokenRankAccumulator(ks, cfg.vocab)
    acc.update(ranks, nll_sum=float(nll[v_flat].sum()))
    return acc.result()
