"""Mesh-aware PartitionSpec builders — the ONE place that knows layouts.

Every parameter / optimizer-state / KV-cache / batch layout in the repo
is produced here; ``launch/cells.py`` and ``launch/steps.py`` contain no
ad-hoc ``PartitionSpec`` construction (grep-verifiable). Spec trees
mirror the parameter pytrees 1:1, so ``named_sharding_tree`` can zip them
straight into ``jit`` in/out shardings. The named-axis-mapping idiom
follows Levanter: a family's layout is a function of (config, mesh), not
scattered literals.

Mesh axes (see ``repro/dist/__init__`` and README §Mesh axes):
  * ``data`` (+ optional outer ``pod``) — batch / position rows ``X``;
  * ``model``                          — catalog / vocab rows ``Y``,
    attention heads, FFN hidden, experts (Megatron TP + vocab-parallel).

Divisibility guard: an axis is only assigned to a tensor dim when the
dim divides the axis size product; otherwise that dim is replicated.
This keeps every builder valid on any mesh (2×4 test minis through
2×16×16 production), at worst trading memory for correctness — the same
rule GSPMD applies implicitly, made explicit so layouts stay auditable.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MODEL_AXIS = "model"
DATA_AXES = ("pod", "data")  # outer-to-inner data-parallel axes


# ---------------------------------------------------------------------------
# Axis helpers
# ---------------------------------------------------------------------------
def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel axes present on ``mesh``, outermost first.

    Parameters
    ----------
    mesh : Mesh
        Any mesh built from the ``("pod", "data", "model")`` vocabulary.

    Returns
    -------
    tuple of str
        Subset of ``("pod", "data")`` present on ``mesh`` — returned as
        a tuple so it can be used directly as ONE entry of a
        ``PartitionSpec`` (sharding a single tensor dim over pod×data).
    """
    return tuple(ax for ax in DATA_AXES if ax in mesh.axis_names)


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[ax] for ax in axes)


def _fit(mesh: Mesh, dim: Optional[int], axes):
    """``axes`` if ``dim`` shards evenly over them, else None (replicate)."""
    if axes is None or not axes:
        return None
    if dim is not None and dim % _axes_size(mesh, axes) != 0:
        return None
    return axes


# ---------------------------------------------------------------------------
# Generic specs
# ---------------------------------------------------------------------------
def replicated_spec() -> P:
    """Fully-replicated spec.

    Returns
    -------
    PartitionSpec
        ``P()`` — valid for any rank (trailing dims default to None).
    """
    return P()


def replicated_specs(tree) -> Any:
    """A spec tree of ``P()`` mirroring ``tree``.

    Parameters
    ----------
    tree : pytree
        Any parameter pytree (small replicated params, e.g. the GNN
        family).

    Returns
    -------
    pytree of PartitionSpec
        Same structure, every leaf ``P()``.
    """
    return jax.tree.map(lambda _: P(), tree)


def batch_spec(mesh: Mesh, ndim: int = 1, *, batch_dim: int = 0) -> P:
    """Batch-leading layout — tokens/targets/labels, per-example outputs.

    Parameters
    ----------
    mesh : Mesh
    ndim : int
        Rank of the tensor the spec describes.
    batch_dim : int
        Which dim is the batch dim (default 0).

    Returns
    -------
    PartitionSpec
        Dim ``batch_dim`` sharded over the data axes (pod×data), every
        other dim replicated.
    """
    dims: list = [None] * ndim
    dims[batch_dim] = data_axes(mesh)
    return P(*dims)


def host_batch_slice(global_rows: int, host_id: int, n_hosts: int) -> slice:
    """Axis-0 slice of the GLOBAL batch owned by ``host_id``.

    The multi-host input-pipeline contract (DESIGN.md §8): each host
    feeds ``jax.make_array_from_process_local_data`` exactly the
    contiguous row block ``[host_id·per, (host_id+1)·per)`` of the
    deterministic global batch, ``per = global_rows / n_hosts``. This is
    the same slicing :class:`repro.data.ShardedCursor.shard` performs
    (``tests/test_dist_sharding.py`` pins the two equivalent, so the
    data layer — numpy-pure, no jax import — and the device-placement
    layer can never disagree about which rows a host owns).

    Raises ``ValueError`` when ``global_rows`` is not divisible by
    ``n_hosts`` (elastic restarts must pick host counts that divide the
    global batch) or ``host_id`` is out of range.
    """
    if not 0 <= host_id < n_hosts:
        raise ValueError(f"host_id {host_id} not in [0, {n_hosts})")
    if global_rows % n_hosts:
        raise ValueError(
            f"global batch rows {global_rows} not divisible by "
            f"n_hosts {n_hosts}"
        )
    per = global_rows // n_hosts
    return slice(host_id * per, (host_id + 1) * per)


def catalog_spec(mesh: Mesh, ndim: int = 2) -> P:
    """Vocab-parallel catalog layout.

    Parameters
    ----------
    mesh : Mesh
    ndim : int
        Rank of the table (2 for the ``(C, d)`` embedding table).

    Returns
    -------
    PartitionSpec
        Rows over ``model``, trailing dims replicated — the catalog /
        vocab table slices ``Y`` that the SCE losses, the serve top-k
        and the streaming eval (``repro.eval``) all consume, so
        training, serving and evaluation never reshard the catalog.
    """
    return P(MODEL_AXIS, *([None] * (ndim - 1)))


def named_sharding_tree(mesh: Mesh, spec_tree) -> Any:
    """Zip a spec tree into a ``NamedSharding`` tree on ``mesh``.

    Parameters
    ----------
    mesh : Mesh
    spec_tree : pytree of PartitionSpec
        Usually the output of one of the ``*_specs`` builders; the tree
        mirrors the parameter pytree 1:1.

    Returns
    -------
    pytree of NamedSharding
        Same structure; pass directly as ``jit`` in/out shardings.
    """
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# LM transformer family
# ---------------------------------------------------------------------------
def residual_act_spec(mesh: Mesh, *, seq_parallel: bool = False):
    """Residual-stream constraint for prefill: with sequence parallelism
    the (B, S, D) stream pins S to ``model`` so per-layer K/V are born in
    the cache layout; otherwise no constraint (GSPMD propagates)."""
    if not seq_parallel:
        return None
    return P(data_axes(mesh), MODEL_AXIS, None)


def lm_tokens_spec(mesh: Mesh, *, seq_parallel: bool = False) -> P:
    """(B, S) token batches: batch over the data axes; with sequence
    parallelism S additionally shards over ``model``.

    Returns
    -------
    PartitionSpec for a rank-2 token tensor.
    """
    return (
        P(data_axes(mesh), MODEL_AXIS)
        if seq_parallel
        else batch_spec(mesh, 2)
    )


def lm_logits_spec(mesh: Mesh, *, seq_shard: bool = False) -> P:
    """(B, 1, V) decode/prefill logits: vocab over ``model``; batch over
    data unless the whole batch is one sequence (long-context decode)."""
    if seq_shard:
        return P(None, None, MODEL_AXIS)
    return P(data_axes(mesh), None, MODEL_AXIS)


def transformer_param_specs(
    cfg, mesh: Mesh, *, fsdp: bool = False, inference: bool = False
) -> Dict[str, Any]:
    """Spec tree mirroring ``models.transformer.init_params``.

    Tensor parallelism (always): vocab rows, attention head dims, FFN
    hidden and experts shard over ``model`` (Megatron layout: column-
    parallel wq/wk/wv/w_gate/w_up, row-parallel wo/w_down).

    ``fsdp=True`` additionally shards the complementary dim of every
    large matrix over the data axes (ZeRO-3 resident weights; gathered
    per layer by GSPMD). ``inference=True`` documents the serve-path
    variant: the cell builder decides whether weights stay FSDP-sharded
    at inference (see the §Perf B1 note in cells.py) and passes the
    outcome via ``fsdp`` — the spec layout itself is identical, which is
    exactly the point: one function owns the family's layout.
    """
    del inference  # layout is fsdp-driven; kwarg kept for call-site intent
    dp = data_axes(mesh) if fsdp else None
    d = cfg.d_model

    def tp(dim):
        return _fit(mesh, dim, MODEL_AXIS)

    def fs(dim):
        return _fit(mesh, dim, dp)

    def attn() -> Dict[str, Any]:
        h, m = cfg.n_heads, cfg.mla
        if m is None:
            hqd = h * cfg.head_dim
            hkvd = cfg.n_kv_heads * cfg.head_dim
            return {
                "wq": P(None, fs(d), tp(hqd)),
                "wk": P(None, fs(d), tp(hkvd)),
                "wv": P(None, fs(d), tp(hkvd)),
                "wo": P(None, tp(hqd), fs(d)),
            }
        # latent attention: the latent projections are small and stay
        # replicated; the per-head up-projections shard by heads
        out = {
            "wkv_a": P(None, fs(d), None),
            "kv_norm": P(None, None),
            "wkv_b": P(None, None,
                       tp(h * (m.qk_nope_head_dim + m.v_head_dim))),
            "wo": P(None, tp(h * m.v_head_dim), fs(d)),
        }
        if m.q_lora_rank:
            out["wq_a"] = P(None, fs(d), None)
            out["q_norm"] = P(None, None)
            out["wq_b"] = P(None, None, tp(h * m.qk_head_dim))
        else:
            out["wq"] = P(None, fs(d), tp(h * m.qk_head_dim))
        return out

    def layer_specs(moe: bool) -> Dict[str, Any]:
        layers = attn()
        layers["norm_attn"] = P(None, None)
        layers["norm_mlp"] = P(None, None)
        if cfg.use_post_norm:
            layers["norm_attn_post"] = P(None, None)
            layers["norm_mlp_post"] = P(None, None)
        if moe:
            e = cfg.moe.held
            f = cfg.moe.d_ff
            spec: Dict[str, Any] = {
                "router": P(None, None, None),  # tiny; replicated for routing
                "w_gate": P(None, tp(e), fs(d), None),
                "w_up": P(None, tp(e), fs(d), None),
                "w_down": P(None, tp(e), None, fs(d)),
            }
            if cfg.moe.n_shared_experts:
                fshared = f * cfg.moe.n_shared_experts
                spec["shared"] = {
                    "w_gate": P(None, fs(d), tp(fshared)),
                    "w_up": P(None, fs(d), tp(fshared)),
                    "w_down": P(None, tp(fshared), fs(d)),
                }
            layers["moe"] = spec
        else:
            ff = cfg.d_ff
            layers["mlp"] = {
                "w_gate": P(None, fs(d), tp(ff)),
                "w_up": P(None, fs(d), tp(ff)),
                "w_down": P(None, tp(ff), fs(d)),
            }
        return layers

    specs: Dict[str, Any] = {
        "embed": P(tp(cfg.vocab_padded), fs(d)),
        "norm_final": P(None),
        "layers": layer_specs(cfg.moe is not None),
    }
    if cfg.n_dense_layers:
        specs["dense_layers"] = layer_specs(False)
    if cfg.moe is not None and cfg.moe.has_bias:
        specs["router_bias"] = P(None, None)
    if not cfg.tie_embeddings:
        specs["unembed"] = P(tp(cfg.vocab_padded), fs(d))
    return specs


def transformer_cache_specs(
    cfg, mesh: Mesh, *, seq_shard: bool = False
) -> Dict[str, P]:
    """Specs for ``models.transformer.init_cache`` trees — one spec per
    ``k{gi}``/``v{gi}`` leaf of shape (n_groups, B, length, H_kv, dh).

    Default: batch over data, KV heads over ``model`` (the layout decode
    attention consumes in place). When the KV head count doesn't divide
    the model axis (GQA minis), the cache length shards over ``model``
    instead. ``seq_shard=True`` (single-sequence long-context decode)
    forces the length dim over ALL axes — the 500k-token cache is the
    only tensor in that cell worth sharding.
    """
    dp = data_axes(mesh)
    if seq_shard:
        spec = P(None, None, dp + (MODEL_AXIS,), None, None)
    elif _fit(mesh, cfg.n_kv_heads, MODEL_AXIS):
        spec = P(None, dp, None, MODEL_AXIS, None)
    else:
        spec = P(None, dp, MODEL_AXIS, None, None)
    return {
        f"{kv}{gi}": spec
        for gi in range(len(cfg.attn_pattern))
        for kv in ("k", "v")
    }


# ---------------------------------------------------------------------------
# Sequential-recommender family (sasrec / bert4rec)
# ---------------------------------------------------------------------------
def seqrec_param_specs(cfg, mesh: Mesh) -> Dict[str, Any]:
    """Spec tree mirroring ``models.sasrec.init_params``.

    Parameters
    ----------
    cfg : SeqRecConfig
        Supplies ``n_rows`` (padded catalog rows), ``d_model``,
        ``d_ff_actual``.
    mesh : Mesh

    Returns
    -------
    dict
        PartitionSpec tree with the structure of the SASRec/BERT4Rec
        param dict.

    Notes
    -----
    The item-embedding table is the model: its rows (catalog) shard over
    ``model`` — the same vocab-parallel layout the SCE loss, the serve
    top-k and the streaming eval consume, so training, serving and
    evaluation never reshard the catalog. Encoder blocks follow
    Megatron: qkv/w1 column-parallel, wo/w2 row-parallel; biases follow
    their matmul's output dim.
    """
    d = cfg.d_model
    ff = cfg.d_ff_actual

    def tp(dim):
        return _fit(mesh, dim, MODEL_AXIS)

    return {
        "item_emb": P(tp(cfg.n_rows), None),
        "pos_emb": P(None, None),
        "ln_f_g": P(None),
        "ln_f_b": P(None),
        "layers": {
            "wqkv": P(None, None, tp(3 * d)),
            "wo": P(None, tp(d), None),
            "w1": P(None, None, tp(ff)),
            "w2": P(None, tp(ff), None),
            "b1": P(None, tp(ff)),
            "b2": P(None, None),
            "ln1_g": P(None, None),
            "ln1_b": P(None, None),
            "ln2_g": P(None, None),
            "ln2_b": P(None, None),
        },
    }


def seqrec_serve_shardings(cfg, mesh: Mesh) -> Any:
    """``NamedSharding`` tree for the seqrec serving/restore path: the
    checkpointed param tree re-sharded straight into the inference
    layout (catalog rows over ``model``, Megatron layer splits) — what
    ``CheckpointManager.restore_params_latest`` hands the retrieval
    server, so a checkpoint written on *any* training mesh restores
    onto the serving mesh without an intermediate replicated copy."""
    return named_sharding_tree(mesh, seqrec_param_specs(cfg, mesh))


# ---------------------------------------------------------------------------
# CTR recsys family (structure-driven: tables shard, dense nets replicate)
# ---------------------------------------------------------------------------
def recsys_param_specs(params_abs, mesh: Mesh) -> Any:
    """Specs for a CTR model's (abstract) param tree.

    The 10^6–10^8-row embedding tables under the ``"tables"`` key shard
    row-wise over ``model`` (when their vocab divides it); everything
    else — cross/CIN/MLP weights, heads — is small and replicates.
    Structure-driven rather than per-arch so DCN-v2/DLRM/xDeepFM (and
    future CTR models following the ``tables`` convention) share it.
    """

    def leaf_specs(key: str, sub):
        if key == "tables":
            return [
                P(_fit(mesh, t.shape[0], MODEL_AXIS), None) for t in sub
            ]
        return jax.tree.map(lambda a: P(*([None] * a.ndim)), sub)

    assert isinstance(params_abs, dict), type(params_abs)
    return {k: leaf_specs(k, v) for k, v in params_abs.items()}


# ---------------------------------------------------------------------------
# Optimizer state — mirror param specs through any optimizer's state tree
# ---------------------------------------------------------------------------
def _is_optstate(x) -> bool:
    return hasattr(x, "_fields") and {"step", "inner"} <= set(x._fields)


def _leaf_state_spec(state_leaf, p_abs, spec: P, key=None) -> P:
    """Spec for one per-parameter state leaf: same shape → the param's
    spec; row stats (adafactor ``vr``, shape p[:-1]) → spec minus last
    dim; col stats (``vc``, shape p[:-2]+p[-1:]) → spec minus the
    second-to-last dim; scalars/unknown → replicated.

    ``key`` (the factored-stats dict key) takes precedence over shape
    matching: for matrices square on their last two dims the vr/vc
    shapes coincide, and shape alone would hand the column stats the
    row spec (e.g. attention weights with n_heads·head_dim == d_model).
    """
    dims = tuple(spec) + (None,) * (p_abs.ndim - len(tuple(spec)))
    if key == "vr" and tuple(state_leaf.shape) == tuple(p_abs.shape[:-1]):
        return P(*dims[:-1])
    if key == "vc" and tuple(state_leaf.shape) == tuple(
        p_abs.shape[:-2] + p_abs.shape[-1:]
    ):
        return P(*(dims[:-2] + dims[-1:]))
    if tuple(state_leaf.shape) == tuple(p_abs.shape):
        return P(*dims)
    if tuple(state_leaf.shape) == tuple(p_abs.shape[:-1]):
        return P(*dims[:-1])
    if tuple(state_leaf.shape) == tuple(p_abs.shape[:-2] + p_abs.shape[-1:]):
        return P(*(dims[:-2] + dims[-1:]))
    return P(*([None] * state_leaf.ndim))


def _mirror_param_tree(state_tree, params, specs):
    """Walk ``state_tree`` in lockstep with the param tree; state leaves
    may be single arrays OR per-param dicts (adafactor's {vr, vc}/{v})."""
    if isinstance(params, dict):
        assert isinstance(state_tree, dict) and set(state_tree) == set(
            params
        ), (sorted(state_tree), sorted(params))
        return {
            k: _mirror_param_tree(state_tree[k], params[k], specs[k])
            for k in state_tree
        }
    if isinstance(params, (list, tuple)):
        assert len(state_tree) == len(params)
        return type(params)(
            _mirror_param_tree(s, p, c)
            for s, p, c in zip(state_tree, params, specs)
        )
    # params is a leaf
    if isinstance(state_tree, dict):  # factored stats
        return {
            k: _leaf_state_spec(v, params, specs, key=k)
            for k, v in state_tree.items()
        }
    return _leaf_state_spec(state_tree, params, specs)


def _matches_params(sub, params) -> bool:
    """Does ``sub`` look like a param-structured tree at its root?"""
    if isinstance(params, dict):
        return isinstance(sub, dict) and set(sub) == set(params)
    if isinstance(params, (list, tuple)):
        return isinstance(sub, (list, tuple)) and len(sub) == len(params)
    return True


def opt_state_specs(
    optimizer_name: str, params_abs, param_specs, opt_state_abs
) -> Any:
    """Spec tree for an (abstract) optimizer state, mirroring the param
    specs through it.

    Parameters
    ----------
    optimizer_name : str
        Advisory only (the walk is structure-driven); kept so call
        sites state intent.
    params_abs : pytree of ShapeDtypeStruct
        Abstract params the state was built for.
    param_specs : pytree of PartitionSpec
        Output of the matching ``*_param_specs`` builder.
    opt_state_abs : pytree
        Abstract optimizer state (``jax.eval_shape`` of ``opt_init``).

    Returns
    -------
    pytree of PartitionSpec
        Same structure as ``opt_state_abs``: adamw/sgd moments inherit
        their param's spec; adafactor row/col stats inherit the
        matching reduced spec (``vr``/``vc`` keys disambiguate square
        matrices); the error-feedback wrapper's residual mirrors the
        gradients; wrapper containers (e.g. ``inner["base"]``) recurse;
        scalars replicate.
    """
    del optimizer_name

    def rec(sub):
        if _is_optstate(sub):
            return type(sub)(step=P(), inner=rec(sub.inner))
        if _matches_params(sub, params_abs):
            return _mirror_param_tree(sub, params_abs, param_specs)
        if isinstance(sub, dict):  # wrapper container ("base"/"ef"/…)
            return {k: rec(v) for k, v in sub.items()}
        return P(*([None] * getattr(sub, "ndim", 0)))

    return rec(opt_state_abs)
