"""Unit tests for the repro.dist sharding-spec builders (DESIGN.md §7).

These run single-device: PartitionSpec trees are pure metadata, so
structure/derivation rules are checkable without a multi-device mesh."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.dist.sharding import (
    batch_spec,
    catalog_spec,
    data_axes,
    host_batch_slice,
    named_sharding_tree,
    opt_state_specs,
    recsys_param_specs,
    replicated_specs,
    seqrec_param_specs,
    transformer_cache_specs,
    transformer_param_specs,
)


@pytest.fixture
def mesh():
    # single device reshaped as (1, 1) — axis names are what matter
    return make_mesh((1, 1), ("data", "model"))


def _tree_struct(tree):
    return jax.tree.structure(
        tree, is_leaf=lambda s: isinstance(s, P)
    )


def test_data_axes_ordering(mesh):
    assert data_axes(mesh) == ("data",)
    mesh3 = make_mesh((1, 1, 1), ("pod", "data", "model"))
    assert data_axes(mesh3) == ("pod", "data")


def test_batch_and_catalog_specs(mesh):
    assert batch_spec(mesh, 3) == P(("data",), None, None)
    assert batch_spec(mesh, 2, batch_dim=1) == P(None, ("data",))
    assert catalog_spec(mesh) == P("model", None)


def test_host_batch_slice_partitions_rows():
    import numpy as np

    rows = 12
    for n_hosts in (1, 2, 3, 4, 6):
        slices = [host_batch_slice(rows, h, n_hosts) for h in range(n_hosts)]
        covered = np.concatenate([np.arange(rows)[s] for s in slices])
        assert covered.tolist() == list(range(rows))  # exact partition
    with pytest.raises(ValueError):
        host_batch_slice(12, 0, 5)  # non-divisible
    with pytest.raises(ValueError):
        host_batch_slice(12, 4, 4)  # host_id out of range


def test_host_batch_slice_matches_sharded_cursor():
    """The device-placement slice and the data layer's ShardedCursor
    slicing must agree row-for-row (DESIGN.md §8: one ownership rule)."""
    import numpy as np

    from repro.data import Cursor, ShardedCursor

    batch = {"x": np.arange(24).reshape(8, 3), "y": np.arange(8)}
    for n_hosts in (1, 2, 4):
        for h in range(n_hosts):
            sc = ShardedCursor(Cursor(seed=0), host_id=h, n_hosts=n_hosts)
            via_cursor = sc.shard(batch)
            sl = host_batch_slice(8, h, n_hosts)
            for k in batch:
                assert (via_cursor[k] == batch[k][sl]).all()


def test_seqrec_specs_mirror_params(mesh):
    from repro.configs import get_arch
    from repro.models import sasrec

    cfg = get_arch("sasrec-sce").make_smoke_config()
    params = jax.eval_shape(
        lambda k: sasrec.init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    specs = seqrec_param_specs(cfg, mesh)
    assert _tree_struct(specs) == jax.tree.structure(params)
    assert specs["item_emb"][0] == "model"  # vocab-parallel catalog
    # NamedSharding zip works over the whole tree
    ns = named_sharding_tree(mesh, specs)
    assert jax.tree.structure(ns) == jax.tree.structure(params)


def test_transformer_specs_mirror_params_and_fsdp(mesh):
    from repro.configs import get_arch
    from repro.models import transformer

    cfg = get_arch("gemma2-2b").make_smoke_config()
    params = jax.eval_shape(
        lambda k: transformer.init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    for fsdp in (False, True):
        specs = transformer_param_specs(cfg, mesh, fsdp=fsdp)
        assert _tree_struct(specs) == jax.tree.structure(params)
    # fsdp shards the complementary dim of the column-parallel matmuls
    specs = transformer_param_specs(cfg, mesh, fsdp=True)
    # (PartitionSpec normalizes a one-axis tuple entry to the bare name.)
    assert specs["layers"]["wq"][1] == P(None, ("data",))[1]
    no_fsdp = transformer_param_specs(cfg, mesh, fsdp=False)
    assert no_fsdp["layers"]["wq"][1] is None
    # cache specs: one entry per k/v per pattern slot, 5-dim specs
    cache = transformer_cache_specs(cfg, mesh)
    assert set(cache) == {
        f"{kv}{gi}" for gi in range(len(cfg.attn_pattern)) for kv in "kv"
    }


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b", "kimi-k2-1t-a32b",
                                  "granite-moe-3b-a800m"])
def test_transformer_specs_mirror_latent_and_moe_params(mesh, arch):
    """Latent attention (with and without a q LoRA), leading dense
    layers, held experts and the router's selection bias."""
    from repro.configs import get_arch
    from repro.models import transformer

    cfg = get_arch(arch).make_smoke_config()
    params = jax.eval_shape(
        lambda k: transformer.init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    for fsdp in (False, True):
        specs = transformer_param_specs(cfg, mesh, fsdp=fsdp)
        assert _tree_struct(specs) == jax.tree.structure(params)


def test_opt_state_specs_adamw_and_sgd(mesh):
    from repro.optim import adamw, sgd_momentum

    params = {"emb": jnp.zeros((16, 4)), "head": {"w": jnp.zeros((4, 2))}}
    p_specs = {"emb": P("model", None), "head": {"w": P(None, None)}}
    for opt_name, (init, _) in (
        ("adamw", adamw(0.1)),
        ("sgd", sgd_momentum(0.1)),
    ):
        state = jax.eval_shape(init, params)
        o_specs = opt_state_specs(opt_name, params, p_specs, state)
        assert o_specs.step == P()
        for moments in o_specs.inner.values():
            assert moments["emb"] == P("model", None)  # mirrors the param
            assert moments["head"]["w"] == P(None, None)


def test_opt_state_specs_adafactor_factored(mesh):
    from repro.optim import adafactor

    init, _ = adafactor(1e-2)
    params = {"emb": jnp.zeros((4096, 512)), "b": jnp.zeros((8,))}
    p_specs = {"emb": P("model", None), "b": P(None)}
    state = jax.eval_shape(init, params)
    o_specs = opt_state_specs("adafactor", params, p_specs, state)
    leaf = o_specs.inner["v"]["emb"]
    assert leaf["vr"] == P("model")  # row stats keep the row sharding
    assert leaf["vc"] == P(None)  # col stats drop it
    assert o_specs.inner["v"]["b"]["v"] == P(None)


def test_opt_state_specs_adafactor_square_matrix(mesh):
    """Square last-two-dims (attention weights with n_heads·head_dim ==
    d_model, the 1T Adafactor arch): vr/vc SHAPES coincide, so the spec
    must come from the dict key, not shape matching — vc follows the
    column sharding, vr the row sharding."""
    from repro.optim import adafactor

    init, _ = adafactor(1e-2)
    params = {"wq": jnp.zeros((3, 256, 256))}
    p_specs = {"wq": P(None, ("data",), "model")}
    state = jax.eval_shape(init, params)
    o_specs = opt_state_specs("adafactor", params, p_specs, state)
    leaf = o_specs.inner["v"]["wq"]
    assert leaf["vr"] == P(None, ("data",))  # mean over cols → row spec
    assert leaf["vc"] == P(None, "model")  # mean over rows → col spec


def test_opt_state_specs_error_feedback_wrapper(mesh):
    from repro.optim import adamw, with_error_feedback_compression

    init, _ = with_error_feedback_compression(adamw(0.1))
    params = {"w": jnp.zeros((16, 4))}
    p_specs = {"w": P("model", None)}
    state = jax.eval_shape(init, params)
    o_specs = opt_state_specs("adamw", params, p_specs, state)
    assert o_specs.inner["ef"]["w"] == P("model", None)  # residual ≅ grads
    assert o_specs.inner["base"]["m"]["w"] == P("model", None)
    # the spec tree zips against the real state tree
    ns = named_sharding_tree(mesh, o_specs)
    assert jax.tree.structure(ns) == jax.tree.structure(state)


def test_recsys_specs_divisibility_guard():
    import types

    # spec builders only read mesh.shape / mesh.axis_names, so a stub
    # lets us exercise the 16-way guard without 16 devices
    mesh16 = types.SimpleNamespace(
        shape={"data": 1, "model": 16}, axis_names=("data", "model")
    )
    params = {
        "tables": [jnp.zeros((32, 4)), jnp.zeros((7, 4))],
        "mlp": {"w0": jnp.zeros((4, 4))},
    }
    specs = recsys_param_specs(params, mesh16)
    assert specs["tables"][0] == P("model", None)  # 32 % 16 == 0
    assert specs["tables"][1] == P(None, None)  # 7 rows can't shard
    assert specs["mlp"]["w0"] == P(None, None)  # dense nets replicate


def test_replicated_specs_gnn_tree():
    tree = {"a": jnp.zeros((3, 3)), "b": [jnp.zeros(2), jnp.zeros(1)]}
    specs = replicated_specs(tree)
    assert all(
        s == P()
        for s in jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(s, P)
        )
    )
