"""Cell builder: (arch, shape, mesh) → jit-able step + abstract inputs +
shardings. The dry-run, the roofline benchmark, and the perf loop all
consume cells; train.py/serve.py reuse the same step factories with real
arrays.

No real allocation happens here: params/opt-state/caches are
``jax.eval_shape`` trees, batches are ``ShapeDtypeStruct``s.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import get_arch
from repro.configs.common import ArchSpec, ShapeSpec
from repro.dist.sharding import (
    batch_spec,
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
from repro.launch import steps as steps_lib
from repro.launch.mesh import dp_size
from repro.models import bert4rec as b4r_lib
from repro.models import recsys as recsys_lib
from repro.models import sasrec as sasrec_lib
from repro.models import schnet as schnet_lib
from repro.models import transformer as tf_lib


@dataclasses.dataclass
class Cell:
    arch: ArchSpec
    shape: ShapeSpec
    mesh: Mesh
    fn: Callable
    args: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def lower(self):
        jitted = jax.jit(
            self.fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )
        with jax.set_mesh(self.mesh):
            return jitted.lower(*self.args)


def _key_abs():
    return jax.ShapeDtypeStruct((2,), jnp.uint32)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ns(mesh, spec_tree):
    return named_sharding_tree(mesh, spec_tree)


def _metrics_shardings(mesh):
    # Train steps return the guarded-update metrics dict
    # (launch/steps.py::_apply_update_guarded): per-step loss, the
    # on-device skip flag, the global grad norm, and — when the guard
    # policy threads them — the per-kernel numerics sentinel counters.
    # All scalars; a single replicated leaf is a jit out_shardings
    # pytree PREFIX covering the whole dict, so the spec stays correct
    # whether or not the optional "sentinels" subtree is present.
    return replicated_sharding(mesh)


def _abs_params(init_fn):
    return jax.eval_shape(init_fn, _key_abs())


# ---------------------------------------------------------------------------
# LM transformer cells
# ---------------------------------------------------------------------------
def _lm_cell(arch: ArchSpec, shape: ShapeSpec, mesh: Mesh, **opts) -> Cell:
    cfg = arch.make_config(shape.name)
    params_abs = _abs_params(functools.partial(tf_lib.init_params, cfg=cfg))
    # §Perf iteration B1 (refuted): dropping FSDP at inference ("TP-
    # resident weights") saves only ~2% wire — the dominant prefill
    # collective is the Megatron TP activation gather, not weights — while
    # costing ~4 GB/device resident memory. Default keeps FSDP;
    # serve_fsdp_threshold>0 re-enables the variant for measurement.
    inference = shape.kind in ("prefill", "decode")
    dtype_bytes = 2 if "16" in arch.dtype else 4
    tp_resident_bytes = cfg.param_count() * dtype_bytes / mesh.shape["model"]
    fsdp_eff = arch.fsdp and not (
        inference
        and tp_resident_bytes < opts.get("serve_fsdp_threshold", 0)
    )
    p_specs = transformer_param_specs(
        cfg, mesh, fsdp=fsdp_eff, inference=inference
    )
    gb = shape.dims["global_batch"]
    seq = shape.dims["seq_len"]
    n_micro = max(
        1,
        min(
            opts.get("n_micro") or arch.microbatches.get(shape.name, 1),
            gb // dp_size(mesh),
        ),
    )

    if shape.kind == "train":
        fn, (opt_init, _), sce_cfg = steps_lib.make_lm_train_step(
            arch, cfg, mesh, shape,
            sce_mode=opts.get("sce_mode", "union"),
            n_micro_override=opts.get("n_micro"),
            bucket_size_y=opts.get("bucket_size_y"),
        )
        opt_abs = jax.eval_shape(opt_init, params_abs)
        o_specs = opt_state_specs(arch.optimizer, params_abs, p_specs, opt_abs)
        batch_abs = {
            "tokens": _sds((gb, seq), jnp.int32),
            "targets": _sds((gb, seq), jnp.int32),
            "valid": _sds((gb, seq), jnp.bool_),
        }
        b_specs = {k: batch_spec(mesh, v.ndim) for k, v in batch_abs.items()}
        return Cell(
            arch, shape, mesh, fn,
            args=(params_abs, opt_abs, batch_abs, _key_abs()),
            in_shardings=(
                _ns(mesh, p_specs), _ns(mesh, o_specs),
                _ns(mesh, b_specs), replicated_sharding(mesh),
            ),
            out_shardings=(
                _ns(mesh, p_specs), _ns(mesh, o_specs),
                _metrics_shardings(mesh),
            ),
            donate_argnums=(0, 1),
            meta={
                "sce": dataclasses.asdict(sce_cfg),
                "sce_mode": opts.get("sce_mode", "union"),
                "params": cfg.param_count(),
                "active_params": cfg.active_param_count(),
                "tokens_per_step": gb * seq,
                # XLA cost analysis counts while-loop bodies ONCE; the
                # dominant nest here is layer-scan × microbatch-scan
                "loop_multiplier": cfg.n_groups * n_micro,
            },
        )

    if shape.kind == "prefill":
        # sequence parallelism (§Perf): pin the residual stream's sequence
        # dim to 'model' so per-layer K/V are born in the cache layout —
        # no batch→seq reshard all-gathers
        seq_par = bool(opts.get("seq_parallel"))
        act_spec = residual_act_spec(mesh, seq_parallel=seq_par)
        fn = steps_lib.make_lm_prefill_step(cfg, act_spec=act_spec)
        tokens_abs = _sds((gb, seq), jnp.int32)
        cache_specs = transformer_cache_specs(cfg, mesh)
        logits_spec = lm_logits_spec(mesh)
        tok_spec = lm_tokens_spec(mesh, seq_parallel=seq_par)
        return Cell(
            arch, shape, mesh, fn,
            args=(params_abs, tokens_abs),
            in_shardings=(
                _ns(mesh, p_specs), _ns(mesh, tok_spec)
            ),
            out_shardings=(
                _ns(mesh, logits_spec), _ns(mesh, cache_specs)
            ),
            meta={"params": cfg.param_count(),
                  "tokens_per_step": gb * seq,
                  "loop_multiplier": cfg.n_groups},
        )

    # decode (decode_32k / long_500k)
    fn = steps_lib.make_lm_decode_step(cfg)
    seq_shard = shape.name == "long_500k"
    cache_abs = jax.eval_shape(
        lambda: tf_lib.init_cache(cfg, gb, seq)
    )
    cache_specs = transformer_cache_specs(cfg, mesh, seq_shard=seq_shard)
    tokens_abs = _sds((gb, 1), jnp.int32)
    pos_abs = _sds((), jnp.int32)
    logits_spec = lm_logits_spec(mesh, seq_shard=seq_shard)
    return Cell(
        arch, shape, mesh, fn,
        args=(params_abs, cache_abs, tokens_abs, pos_abs),
        in_shardings=(
            _ns(mesh, p_specs),
            _ns(mesh, cache_specs),
            _ns(
                mesh,
                replicated_spec() if seq_shard else batch_spec(mesh, 2),
            ),
            replicated_sharding(mesh),
        ),
        out_shardings=(
            _ns(mesh, logits_spec), _ns(mesh, cache_specs)
        ),
        donate_argnums=(1,),
        meta={"params": cfg.param_count(), "kv_positions": gb * seq,
              "loop_multiplier": cfg.n_groups},
    )


# ---------------------------------------------------------------------------
# Sequential-recommender cells (bert4rec / sasrec-sce)
# ---------------------------------------------------------------------------
def _seqrec_cell(arch: ArchSpec, shape: ShapeSpec, mesh: Mesh, **opts) -> Cell:
    cfg = arch.make_config(shape.name)
    init_fn = (
        b4r_lib.init_params if not cfg.causal else sasrec_lib.init_params
    )
    params_abs = _abs_params(functools.partial(init_fn, cfg=cfg))
    p_specs = seqrec_param_specs(cfg, mesh)
    bidirectional = not cfg.causal

    if shape.kind == "train":
        fn, (opt_init, _), sce_cfg = steps_lib.make_seqrec_train_step(
            arch, cfg, mesh, shape,
            sce_mode=opts.get("sce_mode", "exact"),
        )
        opt_abs = jax.eval_shape(opt_init, params_abs)
        o_specs = opt_state_specs(arch.optimizer, params_abs, p_specs, opt_abs)
        gb = shape.dims.get("batch")
        batch_abs = {"tokens": _sds((gb, cfg.max_len), jnp.int32)}
        if not bidirectional:
            batch_abs["targets"] = _sds((gb, cfg.max_len), jnp.int32)
            batch_abs["valid"] = _sds((gb, cfg.max_len), jnp.bool_)
        b_specs = {k: batch_spec(mesh, v.ndim) for k, v in batch_abs.items()}
        return Cell(
            arch, shape, mesh, fn,
            args=(params_abs, opt_abs, batch_abs, _key_abs()),
            in_shardings=(
                _ns(mesh, p_specs), _ns(mesh, o_specs),
                _ns(mesh, b_specs), replicated_sharding(mesh),
            ),
            out_shardings=(
                _ns(mesh, p_specs), _ns(mesh, o_specs),
                _metrics_shardings(mesh),
            ),
            donate_argnums=(0, 1),
            meta={
                "sce": dataclasses.asdict(sce_cfg),
                "sce_mode": opts.get("sce_mode", "exact"),
                "params": cfg.param_count(),
                "catalog": cfg.n_items,
                "loop_multiplier": cfg.n_layers
                * max(1, min(arch.microbatches.get(shape.name, 1),
                             gb // dp_size(mesh))),
            },
        )

    if shape.kind == "serve":
        gb = shape.dims["batch"]
        serve_block_c = 512
        fn = steps_lib.make_seqrec_mips_serve_step(
            arch, cfg, mesh, block_c=serve_block_c
        )
        tokens_abs = _sds((gb, cfg.max_len), jnp.int32)
        tp = mesh.shape.get("model", 1)
        c_local = max(1, cfg.catalog_loss_size // tp)
        return Cell(
            arch, shape, mesh, fn,
            args=(params_abs, tokens_abs),
            in_shardings=(
                _ns(mesh, p_specs), _ns(mesh, batch_spec(mesh, 2))
            ),
            out_shardings=(
                _ns(mesh, batch_spec(mesh, 2)),
                _ns(mesh, batch_spec(mesh, 2)),
            ),
            meta={"params": cfg.param_count(), "catalog": cfg.n_items,
                  "serve_impl": "mips_topk",
                  "serve_buckets": sorted(
                      s.dims["batch"] for s in arch.shapes
                      if s.kind == "serve"
                  ),
                  # dominant loop: the streaming top-k scan over
                  # local-catalog tiles (no (B, C) score slice)
                  "loop_multiplier": -(-c_local // serve_block_c)},
        )

    # retrieval_cand
    n_cand = shape.dims["n_candidates"]
    fn = steps_lib.make_seqrec_retrieval_step(arch, cfg, mesh)
    tokens_abs = _sds((shape.dims["batch"], cfg.max_len), jnp.int32)
    cand_abs = _sds((n_cand,), jnp.int32)
    return Cell(
        arch, shape, mesh, fn,
        args=(params_abs, tokens_abs, cand_abs),
        in_shardings=(
            _ns(mesh, p_specs),
            replicated_sharding(mesh),
            replicated_sharding(mesh),
        ),
        out_shardings=(
            replicated_sharding(mesh), replicated_sharding(mesh)
        ),
        meta={"params": cfg.param_count(), "n_candidates": n_cand,
              "loop_multiplier": cfg.n_layers},
    )


# ---------------------------------------------------------------------------
# CTR recsys cells
# ---------------------------------------------------------------------------
def _recsys_init_fn(arch_name: str):
    return {
        "dcn-v2": recsys_lib.init_dcn_v2,
        "dlrm-rm2": recsys_lib.init_dlrm,
        "xdeepfm": recsys_lib.init_xdeepfm,
    }[arch_name]


def _recsys_cell(arch: ArchSpec, shape: ShapeSpec, mesh: Mesh, **opts) -> Cell:
    cfg = arch.make_config(shape.name)
    init_fn = _recsys_init_fn(arch.name)
    params_abs = _abs_params(functools.partial(init_fn, cfg=cfg))
    p_specs = recsys_param_specs(params_abs, mesh)
    n_dense = getattr(cfg, "n_dense", 1)
    n_fields = len(cfg.vocab_sizes)
    hot = cfg.hot

    def batch_abs_for(b):
        return {
            "dense": _sds((b, n_dense), jnp.float32),
            "sparse_ids": _sds((b, n_fields, hot), jnp.int32),
            "labels": _sds((b,), jnp.float32),
        }

    if shape.kind == "train":
        fn, (opt_init, _) = steps_lib.make_recsys_train_step(
            arch, cfg, mesh, shape
        )
        opt_abs = jax.eval_shape(opt_init, params_abs)
        o_specs = opt_state_specs(arch.optimizer, params_abs, p_specs, opt_abs)
        gb = shape.dims["batch"]
        batch_abs = batch_abs_for(gb)
        b_specs = {k: batch_spec(mesh, v.ndim) for k, v in batch_abs.items()}
        return Cell(
            arch, shape, mesh, fn,
            args=(params_abs, opt_abs, batch_abs, _key_abs()),
            in_shardings=(
                _ns(mesh, p_specs), _ns(mesh, o_specs),
                _ns(mesh, b_specs), replicated_sharding(mesh),
            ),
            out_shardings=(
                _ns(mesh, p_specs), _ns(mesh, o_specs),
                _metrics_shardings(mesh),
            ),
            donate_argnums=(0, 1),
            meta={
                "params": cfg.param_count(),
                "embedding_rows": sum(cfg.vocab_sizes),
                "loop_multiplier": 1,  # no scans in the CTR train step
            },
        )

    if shape.kind == "serve":
        gb = shape.dims["batch"]
        fn = steps_lib.make_recsys_serve_step(arch, cfg)
        b = batch_abs_for(gb)
        return Cell(
            arch, shape, mesh, fn,
            args=(params_abs, b["dense"], b["sparse_ids"]),
            in_shardings=(
                _ns(mesh, p_specs),
                _ns(mesh, batch_spec(mesh, 2)),
                _ns(mesh, batch_spec(mesh, 3)),
            ),
            out_shardings=_ns(mesh, batch_spec(mesh, 1)),
            meta={"params": cfg.param_count(), "loop_multiplier": 1},
        )

    # retrieval_cand: one user, 10^6 candidates substituted into field 0
    n_cand = shape.dims["n_candidates"]
    fn = steps_lib.make_recsys_retrieval_step(arch, cfg)
    return Cell(
        arch, shape, mesh, fn,
        args=(
            params_abs,
            _sds((1, n_dense), jnp.float32),
            _sds((1, n_fields, hot), jnp.int32),
            _sds((n_cand,), jnp.int32),
        ),
        in_shardings=(
            _ns(mesh, p_specs),
            replicated_sharding(mesh),
            replicated_sharding(mesh),
            replicated_sharding(mesh),
        ),
        out_shardings=(
            replicated_sharding(mesh), replicated_sharding(mesh)
        ),
        meta={"params": cfg.param_count(), "n_candidates": n_cand,
              # lax.map over candidate chunks of 4096
              "loop_multiplier": -(-n_cand // 4096)},
    )


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------
def _gnn_cell(arch: ArchSpec, shape: ShapeSpec, mesh: Mesh, **opts) -> Cell:
    cfg = arch.make_config(shape.name)
    params_abs = _abs_params(
        functools.partial(schnet_lib.init_params, cfg=cfg)
    )
    p_specs = replicated_specs(params_abs)
    dims = shape.dims

    fn, (opt_init, _) = steps_lib.make_gnn_train_step(arch, cfg, mesh, shape)
    opt_abs = jax.eval_shape(opt_init, params_abs)
    o_specs = opt_state_specs(arch.optimizer, params_abs, p_specs, opt_abs)

    if shape.kind == "train_sampled":
        bn = dims["batch_nodes"]
        fan = (dims["fanout0"], dims["fanout1"])
        import numpy as _np

        max_nodes = bn * (1 + int(_np.prod(fan)) * 2)
        n_edges = bn * fan[0] + bn * fan[0] * fan[1]
        batch_abs = {
            "node_feats": _sds((max_nodes, dims["d_feat"]), jnp.float32),
            "positions": _sds((max_nodes, 3), jnp.float32),
            "edge_index": _sds((2, n_edges), jnp.int32),
            "edge_valid": _sds((n_edges,), jnp.bool_),
            "seed_local": _sds((bn,), jnp.int32),
            "targets": _sds((bn,), jnp.float32),
        }
        b_specs = {
            # sampled subgraph: only edges shard (messages are the work)
            "node_feats": replicated_spec(),
            "positions": replicated_spec(),
            "edge_index": batch_spec(mesh, 2, batch_dim=1),
            "edge_valid": batch_spec(mesh, 1),
            "seed_local": replicated_spec(),
            "targets": replicated_spec(),
        }
    elif shape.name == "molecule":
        b = dims["batch"]
        n_total = b * dims["n_nodes"]
        n_e = b * dims["n_edges"] * 2  # symmetrized
        batch_abs = {
            "node_feats": _sds((n_total, dims["d_feat"]), jnp.float32),
            "positions": _sds((n_total, 3), jnp.float32),
            "edge_index": _sds((2, n_e), jnp.int32),
            "graph_ids": _sds((n_total,), jnp.int32),
            "targets": _sds((b,), jnp.float32),
        }
        b_specs = {
            "node_feats": batch_spec(mesh, 2),
            "positions": batch_spec(mesh, 2),
            "edge_index": batch_spec(mesh, 2, batch_dim=1),
            "graph_ids": batch_spec(mesh, 1),
            "targets": batch_spec(mesh, 1),
        }
    else:  # full-batch graphs (full_graph_sm, ogb_products)
        n, e = dims["n_nodes"], dims["n_edges"]
        # pad node/edge counts to shard evenly on any production mesh;
        # node_valid/edge_valid mask the padding out of loss and messages
        n_pad = -(-n // 512) * 512
        e_pad = -(-e // 512) * 512
        big = n > 100_000
        batch_abs = {
            "node_feats": _sds((n_pad, dims["d_feat"]), jnp.float32),
            "positions": _sds((n_pad, 3), jnp.float32),
            "edge_index": _sds((2, e_pad), jnp.int32),
            "edge_valid": _sds((e_pad,), jnp.bool_),
            "node_valid": _sds((n_pad,), jnp.bool_),
            "targets": _sds((n_pad,), jnp.float32),
        }
        node_spec = batch_spec(mesh, 2) if big else replicated_spec()
        node_vec = batch_spec(mesh, 1) if big else replicated_spec()
        b_specs = {
            "node_feats": node_spec,
            "positions": node_spec,
            "edge_index": batch_spec(mesh, 2, batch_dim=1),
            "edge_valid": batch_spec(mesh, 1),
            "node_valid": node_vec,
            "targets": node_vec,
        }

    return Cell(
        arch, shape, mesh, fn,
        args=(params_abs, opt_abs, batch_abs, _key_abs()),
        in_shardings=(
            _ns(mesh, p_specs), _ns(mesh, o_specs),
            _ns(mesh, b_specs), replicated_sharding(mesh),
        ),
        out_shardings=(
            _ns(mesh, p_specs), _ns(mesh, o_specs),
            _metrics_shardings(mesh),
        ),
        donate_argnums=(0, 1),
        meta={"params": cfg.param_count(),
              # scan over the interaction blocks
              "loop_multiplier": cfg.n_interactions},
    )


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------
_BUILDERS = {
    "lm": _lm_cell,
    "seqrec": _seqrec_cell,
    "recsys": _recsys_cell,
    "gnn": _gnn_cell,
}


def build_cell(
    arch_name: str, shape_name: str, mesh: Mesh, **opts
) -> Cell:
    arch = get_arch(arch_name)
    shape = arch.shape(shape_name)
    if shape.skip is not None:
        raise ValueError(
            f"cell ({arch_name}, {shape_name}) is a documented skip: "
            f"{shape.skip}"
        )
    return _BUILDERS[arch.family](arch, shape, mesh, **opts)


def all_cells(include_skips: bool = False):
    """Yield (arch_name, shape_name, skip_reason|None) for the full grid."""
    from repro.configs import list_archs

    for arch_name in list_archs():
        arch = get_arch(arch_name)
        for shape in arch.shapes:
            yield arch_name, shape.name, shape.skip
