"""Synthetic sequential-recommendation / LM data.

Generator design (learnable, not just noise): items live in ``n_clusters``
latent clusters; a user follows a sticky Markov chain over clusters and
draws items Zipf-distributed *within* the current cluster. A model that
learns the cluster transitions beats the popularity baseline — giving the
quality benchmarks (paper Figs. 3/6, Tables 2/3) a signal to rank losses
by, while item frequencies stay Zipfian like real catalogs (paper §4.1.1).

Everything is a pure function of ``(seed, step)`` via
:class:`repro.data.pipeline.Cursor` — deterministic, resumable, and
shardable by slicing the batch dimension.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.data.pipeline import Cursor, ShardedCursor


@dataclasses.dataclass(frozen=True)
class SeqDataConfig:
    n_items: int  # catalog size C (0 is reserved for padding)
    seq_len: int
    batch_size: int
    n_clusters: int = 64
    zipf_a: float = 1.2  # within-cluster popularity skew
    stickiness: float = 0.8  # P(stay in current cluster)
    min_len_frac: float = 0.5  # sequences have random length ≥ frac·L
    pad_id: int = 0


class SequenceDataset:
    """``next_batch(cursor) -> (batch, cursor')`` with
    batch = {tokens (B, L) int32, targets (B, L) int32, valid (B, L) bool}.

    ``targets[i, t] = tokens[i, t+1]`` (next-item prediction); the last
    position and padding are invalid.
    """

    def __init__(self, cfg: SeqDataConfig):
        self.cfg = cfg
        # Static catalog structure derived from seed-independent layout:
        # item i belongs to cluster i % n_clusters; popularity rank within
        # a cluster is i // n_clusters. (Static so train/test agree.)
        usable = cfg.n_items - 1  # id 0 = padding
        self._items_per_cluster = max(1, usable // cfg.n_clusters)

    def _sample_items(self, rng, clusters: np.ndarray) -> np.ndarray:
        """Zipf-ranked item within each given cluster id. Vectorized."""
        cfg = self.cfg
        k = self._items_per_cluster
        # Zipf over ranks 0..k-1 (truncated, normalized).
        ranks = np.arange(1, k + 1, dtype=np.float64)
        p = ranks ** (-cfg.zipf_a)
        p /= p.sum()
        rank = rng.choice(k, size=clusters.shape, p=p)
        items = 1 + clusters + rank * cfg.n_clusters  # interleaved layout
        return np.minimum(items, cfg.n_items - 1).astype(np.int32)

    # A profiler span (a no-op unless a trace is being taken); the
    # sharded path runs inside it too, through this method.
    @functools.partial(jax.profiler.annotate_function,
                       name="repro.data.next_batch")
    def next_batch(
        self, cursor: Cursor
    ) -> Tuple[Dict[str, np.ndarray], Cursor]:
        cfg = self.cfg
        rng = cursor.rng(salt=1)
        b, l = cfg.batch_size, cfg.seq_len

        # Sticky Markov chain over clusters.
        clusters = np.empty((b, l), np.int64)
        clusters[:, 0] = rng.integers(0, cfg.n_clusters, size=b)
        stay = rng.random((b, l)) < cfg.stickiness
        jumps = rng.integers(0, cfg.n_clusters, size=(b, l))
        for t in range(1, l):
            clusters[:, t] = np.where(
                stay[:, t], clusters[:, t - 1], jumps[:, t]
            )
        tokens = self._sample_items(rng, clusters)

        # Random sequence lengths (front-padded like SASRec pipelines).
        min_len = max(2, int(cfg.min_len_frac * l))
        lengths = rng.integers(min_len, l + 1, size=b)
        pos = np.arange(l)[None, :]
        is_real = pos >= (l - lengths[:, None])
        tokens = np.where(is_real, tokens, cfg.pad_id).astype(np.int32)

        targets = np.zeros_like(tokens)
        targets[:, :-1] = tokens[:, 1:]
        valid = is_real.copy()
        valid[:, -1] = False
        valid &= targets != cfg.pad_id

        batch = {
            "tokens": tokens,
            "targets": targets,
            "valid": valid,
        }
        return batch, cursor.advance()

    def next_batch_sharded(
        self, scursor: ShardedCursor
    ) -> Tuple[Dict[str, np.ndarray], ShardedCursor]:
        """Host-local rows of the GLOBAL batch at ``scursor``.

        The full global batch is generated (the vectorized Markov/Zipf
        draws are batch-shaped, so row ``i``'s tokens depend on the
        whole-batch draw order) and this host's contiguous row block is
        sliced out — which is exactly what makes the global stream
        bit-identical under resharding. The synthetic generator is
        cheap enough that the (global_batch × L) working set is noise;
        a real ingestion pipeline would key its RNG per row to generate
        only the local slice.
        """
        batch, _ = self.next_batch(scursor.cursor)
        return scursor.shard(batch), scursor.advance()

    def eval_batch(self, cursor: Cursor) -> Tuple[Dict[str, np.ndarray], Cursor]:
        """Held-out batch: same generator, disjoint split → unseen users
        (the seqrec leave-one-out eval stream)."""
        return self.next_batch(cursor.split("eval"))

    def heldout_batch(
        self, cursor: Cursor
    ) -> Tuple[Dict[str, np.ndarray], Cursor]:
        """Held-out token stream for the LM token-rank protocol: same
        generator, disjoint ``"heldout"`` split — every next-token
        position of these sequences is an eval row
        (``repro.eval.evaluate_streaming_lm``), unlike ``eval_batch``
        whose leave-one-out protocol scores one position per user."""
        return self.next_batch(cursor.split("heldout"))


def lm_batch(cursor: Cursor, vocab: int, batch: int, seq_len: int):
    """Plain LM token batch (for the transformer archs' smoke tests):
    same cluster-Markov generator re-used as a pseudo-language."""
    cfg = SeqDataConfig(
        n_items=vocab, seq_len=seq_len, batch_size=batch, min_len_frac=1.0
    )
    ds = SequenceDataset(cfg)
    b, cur = ds.next_batch(cursor)
    return b, cur
