"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel at sasrec-sce's real widths (catalog
C = 173,511, d = 64, train_paper's N = 128·200 positions, SCE
n_b = b_x = 320, b_y = 256, eval/serve top-10), or at the LM head of
moonlight-16b-a3b's cell (a 20,480-row vocabulary slice, d = 2,048,
N = 8,192 positions, n_b = b_x = 181, b_y = 256), for a described — not
attached — v5e chip and compiles it with the TPU compiler, which refuses
what Mosaic cannot lower (block tiling, layouts, VMEM). The block pair
each test compiles is the one the guard's preflight accepts for that
call on a TPU backend, so the preflight's legality and VMEM model are
held to the real compiler. One test compiles moonlight's whole train
step at the cell's sizes and holds its memory to the chip's. Nothing
runs: these say nothing about results or speed.

The topology is described inside a module-scoped fixture (never at
import), and the tests skip when it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import eval_fused, linear_sce, mips_topk, sce_prefetch
from repro.kernels.guard import preflight

C, D = 173_511, 64
N = 128 * 200
N_B, B_X, B_Y = 320, 320, 256
EVAL_USERS, TOP_K = 512, 10
SERVE_ROWS = 8  # the serve path's smallest request bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to a persistent cache
    # but cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


LM_C, LM_D, LM_N = 20_480, 2_048, 8_192
LM_NB, LM_BX, LM_BY = 181, 181, 256


def _blocks(kernel, rows, cols, k=None, block_rows=128, block_cols=512,
            d=D):
    return preflight(
        kernel, rows=rows, cols=cols, d=d, block_rows=block_rows,
        block_cols=block_cols, k=k, backend="tpu",
    ).blocks


F32, I32, BOOL = jnp.float32, jnp.int32, jnp.bool_


@pytest.mark.parametrize("k,rows,with_valid", [
    (B_Y, C, False),   # Y side: catalog candidates
    (B_Y, C, True),
    (B_X, N, True),    # X side: positions, padding masked
    (B_X, N, False),
])
def test_mips_topk_compiles(one_chip, k, rows, with_valid):
    bq, bc = _blocks("mips_topk", N_B, rows, k=k)

    if with_valid:
        def fn(q, y, v):
            return mips_topk.mips_topk(q, y, k, valid=v, block_q=bq,
                                       block_c=bc)
        shapes = [((N_B, D), F32), ((rows, D), F32), ((rows,), BOOL)]
    else:
        def fn(q, y):
            return mips_topk.mips_topk(q, y, k, block_q=bq, block_c=bc)
        shapes = [((N_B, D), F32), ((rows, D), F32)]
    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)


def test_mips_topk_serve_compiles(one_chip):
    """Serve's top-10 of a small batch over the whole catalog."""
    bq, bc = _blocks("mips_topk", SERVE_ROWS, C, k=TOP_K)

    def fn(q, y, v):
        return mips_topk.mips_topk(q, y, TOP_K, valid=v, block_q=bq,
                                   block_c=bc)
    shapes = [((SERVE_ROWS, D), F32), ((C, D), F32), ((C,), BOOL)]
    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)


_GATHER_SHAPES = [
    ((N_B, B_X, D), F32), ((C, D), F32), ((N_B, B_Y), I32),
    ((N_B, B_X), I32), ((N_B, B_Y), I32),
]


@pytest.mark.parametrize("flavour,grad", [
    ("loss", False), ("loss", True), ("plse", False), ("plse", True),
])
def test_sce_gather_compiles(one_chip, flavour, grad):
    bx, by = _blocks("sce_gather", B_X, B_Y)
    shapes = list(_GATHER_SHAPES)
    if flavour == "loss":
        shapes.append(((N_B, B_X), F32))

        def loss(x_b, y, idx, tgt, cand, pos):
            return sce_prefetch.sce_gather_loss(
                x_b, y, idx, tgt, cand, pos, bx, by
            ).sum()
        diff = (0, 1, 5)
    else:
        def loss(x_b, y, idx, tgt, cand):
            return sce_prefetch.sce_gather_plse(
                x_b, y, idx, tgt, cand, bx, by
            ).sum()
        diff = (0, 1)
    fn = jax.grad(loss, argnums=diff) if grad else loss
    text = _compile(fn, one_chip, *shapes)
    # forward, plus the one backward kernel (dX and the dY RMW)
    assert text.count("tpu_custom_call") >= (2 if grad else 1)


@pytest.mark.parametrize("with_lse", [False, True])
def test_eval_fused_compiles(one_chip, with_lse):
    bb, bc = _blocks("eval_fused", EVAL_USERS, C, k=TOP_K)

    def fn(x, y, t):
        return eval_fused.eval_fused(
            x, y, t, TOP_K, block_b=bb, block_c=bc, c_lo=1, c_hi=C,
            with_lse=with_lse,
        )
    text = _compile(fn, one_chip, ((EVAL_USERS, D), F32), ((C, D), F32),
                    ((EVAL_USERS,), I32))
    # the tgt-gather pre-stage and the sweep
    assert text.count("tpu_custom_call") >= 2


def test_eval_fused_traced_offset_compiles(one_chip):
    """A catalog shard's traced id offset runs the kernel, not the ref."""
    shard = C // 4 + 1

    def fn(x, y, t, off):
        return eval_fused.eval_fused(x, y, t, TOP_K, id_offset=off,
                                     c_lo=1, c_hi=C)
    text = _compile(fn, one_chip, ((EVAL_USERS, D), F32), ((shard, D), F32),
                    ((EVAL_USERS,), I32), ((), I32))
    assert "tpu_custom_call" in text


def test_eval_tgt_gather_compiles(one_chip):
    bb, bc = _blocks("eval_fused", EVAL_USERS, C, k=TOP_K)

    def fn(x, y, t):
        return eval_fused.eval_tgt_gather(x, y, t, block_b=bb, block_c=bc)
    assert "tpu_custom_call" in _compile(
        fn, one_chip, ((EVAL_USERS, D), F32), ((C, D), F32),
        ((EVAL_USERS,), I32),
    )


@pytest.mark.parametrize("grad", [False, True])
def test_linear_ce_compiles(one_chip, grad):
    bn, bc = _blocks("linear_sce", N, C, block_rows=256)

    def loss(x, w, t):
        return linear_sce.linear_ce_loss(x, w, t, None, bn, bc).sum()
    fn = jax.grad(loss, argnums=(0, 1)) if grad else loss
    text = _compile(fn, one_chip, ((N, D), F32), ((C, D), F32), ((N,), I32))
    assert text.count("tpu_custom_call") >= (3 if grad else 1)


def test_train_step_scopes(one_chip, topo, monkeypatch):
    """The sasrec-sce smoke train step with its Pallas kernels, compiled
    for one v5e: each ``mips_topk`` call sits under ``select/positions``
    or ``select/catalog``, each ``sce_gather`` call under
    ``gather_loss``, and every other op the profiler can name under one
    of the four scopes (``_scopes.unscoped``)."""
    from jax.sharding import AxisType

    from _scopes import instructions, scope, seqrec_step_text, unscoped
    from repro.kernels import guard, ops

    # the kernels' TPU path, as on the chip; its canaries need a chip
    monkeypatch.setattr(ops, "_default_backend", lambda: "tpu")
    monkeypatch.setattr(guard, "kernel_enabled", lambda *a, **k: True)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=topo.devices[:1])
    text = seqrec_step_text(batch=8, seq_len=32, compile_for=mesh)
    kernels = {}
    for name, kind, _, _, op in instructions(text):
        if kind == "custom-call" and op and "pallas_call" in op:
            kernels.setdefault(name.split(".")[0], set()).add(
                "/".join(scope(op).split("/")[:2]))
    assert kernels == {
        "mips_topk": {"select/positions", "select/catalog"},
        "sce_gather_fwd": {"gather_loss/while"},
        "sce_gather_bwd": {"gather_loss/while"},
    }
    assert unscoped(text) == []


@pytest.mark.parametrize("k,rows,with_valid", [
    (LM_BY, LM_C, False),  # the vocabulary slice
    (LM_BX, LM_N, True),   # the sequence's positions
])
def test_mips_topk_compiles_lm_head(one_chip, k, rows, with_valid):
    bq, bc = _blocks("mips_topk", LM_NB, rows, k=k, d=LM_D)
    assert (bq, bc) == (128, 512)

    def fn(q, y, *v):
        return mips_topk.mips_topk(q, y, k, valid=v[0] if v else None,
                                   block_q=bq, block_c=bc)
    shapes = [((LM_NB, LM_D), F32), ((rows, LM_D), F32)]
    if with_valid:
        shapes.append(((rows,), BOOL))
    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("flavour", ["loss", "plse"])
def test_sce_gather_compiles_lm_head(one_chip, flavour):
    """Rows of 2,048 lanes, gathered through the (C, 1, d) view, with
    the blocks the preflight picks at that width (the Gowalla cell keeps
    its 128 × 256)."""
    bx, by = _blocks("sce_gather", LM_BX, LM_BY, block_cols=256, d=LM_D)
    assert _blocks("sce_gather", B_X, B_Y, block_cols=256) == (128, 256)
    shapes = [((LM_NB, LM_BX, LM_D), F32), ((LM_C, LM_D), F32),
              ((LM_NB, LM_BY), I32), ((LM_NB, LM_BX), I32),
              ((LM_NB, LM_BY), I32)]
    if flavour == "loss":
        shapes.append(((LM_NB, LM_BX), F32))

        def loss(x_b, y, idx, tgt, cand, pos):
            return sce_prefetch.sce_gather_loss(
                x_b, y, idx, tgt, cand, pos, bx, by).sum()
        diff = (0, 1, 5)
    else:
        def loss(x_b, y, idx, tgt, cand):
            return sce_prefetch.sce_gather_plse(
                x_b, y, idx, tgt, cand, bx, by).sum()
        diff = (0, 1)
    text = _compile(jax.grad(loss, argnums=diff), one_chip, *shapes)
    assert text.count("tpu_custom_call") >= 2


@pytest.fixture(scope="module")
def moonlight_step(one_chip, topo):
    """moonlight-16b-a3b's train step at the cell's sizes (its one-chip
    config, one 8,192-token sequence, SCE exact on a 1×1 mesh), with its
    Pallas kernels at the blocks the preflight picks on a TPU, compiled
    for one v5e."""
    import functools

    from jax.sharding import AxisType, NamedSharding, PartitionSpec

    from repro.configs import get_arch
    from repro.kernels import guard, ops
    from repro.launch import steps as steps_lib
    from repro.models import transformer as tf

    with pytest.MonkeyPatch.context() as monkeypatch:
        monkeypatch.setattr(ops, "_default_backend", lambda: "tpu")
        monkeypatch.setattr(guard, "kernel_enabled", lambda *a, **k: True)
        monkeypatch.setattr(guard, "preflight", functools.partial(
            guard.preflight, backend="tpu"))
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=topo.devices[:1])
        arch = get_arch("moonlight-16b-a3b")
        shape = arch.shape("train_8k")
        cfg = arch.make_config(shape.name)
        step, (opt_init, _), _ = steps_lib.make_lm_train_step(
            arch, cfg, mesh, shape, sce_mode="exact")
        rep = NamedSharding(mesh, PartitionSpec())

        def sds(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=rep), tree)

        params = jax.eval_shape(
            lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
        n = shape.dims["seq_len"]
        batch = {k: jax.ShapeDtypeStruct((1, n), dt, sharding=rep)
                 for k, dt in (("tokens", I32), ("targets", I32),
                               ("valid", BOOL))}
        batch["loss_cap"] = jax.ShapeDtypeStruct((), F32, sharding=rep)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        with jax.set_mesh(mesh):
            return jax.jit(step, donate_argnums=(0, 1)).lower(
                sds(params), sds(jax.eval_shape(opt_init, params)), batch,
                key).compile()


def test_moonlight_train_step_fits_one_chip(moonlight_step):
    """moonlight-16b-a3b's train step at the cell's sizes compiles, its
    arguments (weights and AdamW state) and temporaries fit the chip's
    16 GB, and the layers' scopes are in its ops."""
    from _scopes import instructions, scope

    compiled = moonlight_step
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    paths = {scope(op) for *_, op in instructions(compiled.as_text()) if op}
    def under(path, parts):  # ``parts`` in order among path's parts
        it = iter(path.split("/"))
        return all(p in it for p in parts)

    for parts in (("mla",), ("dense_ffn",), ("moe", "route"),
                  ("moe", "experts"), ("moe", "shared")):
        assert any(under(path, parts) for path in paths), parts
    kernels = {name.split(".")[0] for name, kind, *_ in
               instructions(compiled.as_text()) if kind == "custom-call"}
    assert {"mips_topk", "sce_gather_fwd", "sce_gather_bwd"} <= kernels


def test_moonlight_step_flash_kernels_under_mla(moonlight_step):
    """Latent attention in the cell's step runs the three flash kernels,
    forward and backward, each under the ``mla`` scope that
    ``mla_ms_per_step`` reads, found as the benchmark finds it: the
    instructions' ``op_name``s by ``scopes.op_names_from_hlo``."""
    from _scopes import scope
    from harness.scopes import op_names_from_hlo

    scopes = {}
    _, op_names = op_names_from_hlo(moonlight_step.as_text())
    for name, op in op_names.items():
        if name.startswith("mla_flash"):
            scopes.setdefault(name.split(".")[0], set()).add(
                "mla" in scope(op).split("/"))
    assert scopes == {"mla_flash_fwd": {True}, "mla_flash_dq": {True},
                      "mla_flash_dkv": {True}}


@pytest.mark.parametrize("hq,hkv,dqk,dv", [
    (16, 16, 192, 128),  # moonlight's latent attention
    (32, 4, 128, 128),   # grouped-query attention (yi-6b's heads)
])
def test_flash_attention_compiles(one_chip, hq, hkv, dqk, dv):
    """The flash kernels, forward and backward, over one 8,192-token
    sequence with the blocks the preflight accepts on a TPU, at one bf16
    MXU pass."""
    from repro.kernels import flash_attention as fa

    bq, bk = preflight(
        "flash_attention", rows=LM_N, cols=LM_N, d=dqk,
        block_rows=fa.BLOCK_Q, block_cols=fa.BLOCK_KV, backend="tpu",
    ).blocks
    assert (bq, bk) == (fa.BLOCK_Q, fa.BLOCK_KV)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, scale=dqk**-0.5, block_q=bq,
                                  block_kv=bk, mxu_dtype=jnp.bfloat16).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((1, LM_N, hq, dqk), F32), ((1, LM_N, hkv, dqk), F32),
                    ((1, LM_N, hkv, dv), F32))
    for name in ("mla_flash_fwd", "mla_flash_dq", "mla_flash_dkv"):
        assert name in text, name
