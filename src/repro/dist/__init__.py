"""``repro.dist`` — the distribution substrate (DESIGN.md §2/§7).

One package owns how the reproduction spreads over a mesh; everything
above it (loss, cells, steps, serve, dry-run) consumes this API and holds
no layout knowledge of its own.

Mesh axes and what shards over them
-----------------------------------
  ``data``  (+ optional outer ``pod``) — **X rows**: model outputs /
      positions ``(N, d)``, token batches, per-example outputs. The
      ``pod`` axis is an outer tier of the same data parallelism whose
      collectives cross the slower inter-pod links (DCI), so gradient
      reductions are the only traffic placed on it.
  ``model`` — **Y rows**: the catalog / vocabulary table ``(C, d)``
      (vocab parallelism), plus Megatron tensor parallelism inside
      blocks (attention heads, FFN hidden, experts). **Buckets**: SCE
      buckets are drawn per ``data`` shard and, in exact mode, their
      *processing* is split over ``model`` (n_b/m buckets per shard);
      in union mode every shard processes all buckets against its own
      catalog slice.

Modules
-------
  ``sharding``    — mesh-aware PartitionSpec builders for every family's
      params, optimizer state, KV caches and batches; the only place
      layouts are written down.
  ``collectives`` — the two cross-shard exchanges the SCE stack needs
      (exact-mode candidate all_to_all, two-stage serve top-k), with
      single-device fallbacks and trace-time payload-bytes accounting
      consumed by ``launch/dryrun.py``.

Meshes, ``shard_map`` and the ambient mesh use JAX's own spellings
(``jax.make_mesh`` with ``AxisType.Auto`` axes, ``jax.shard_map``,
``jax.set_mesh``).
"""
from repro.dist.collectives import (
    all_to_all_bucket_shuffle,
    distributed_topk,
    distributed_topk_from_local,
    payload_log,
    payload_summary,
    reset_payload_log,
)
from repro.dist.sharding import (
    MODEL_AXIS,
    batch_spec,
    catalog_spec,
    data_axes,
    lm_logits_spec,
    lm_tokens_spec,
    named_sharding_tree,
    opt_state_specs,
    recsys_param_specs,
    replicated_sharding,
    replicated_spec,
    replicated_specs,
    residual_act_spec,
    seqrec_param_specs,
    transformer_cache_specs,
    transformer_param_specs,
)

__all__ = [
    "MODEL_AXIS",
    "all_to_all_bucket_shuffle",
    "batch_spec",
    "catalog_spec",
    "data_axes",
    "distributed_topk",
    "distributed_topk_from_local",
    "lm_logits_spec",
    "lm_tokens_spec",
    "named_sharding_tree",
    "opt_state_specs",
    "payload_log",
    "payload_summary",
    "recsys_param_specs",
    "replicated_sharding",
    "replicated_spec",
    "replicated_specs",
    "reset_payload_log",
    "residual_act_spec",
    "seqrec_param_specs",
    "transformer_cache_specs",
    "transformer_param_specs",
]
