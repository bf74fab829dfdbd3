"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel at sasrec-sce's real widths (catalog
C = 173,511, d = 64, train_paper's N = 128·200 positions, SCE
n_b = b_x = 320, b_y = 256, eval/serve top-10) for a described — not
attached — v5e chip and compiles it with the TPU compiler, which refuses
what Mosaic cannot lower (block tiling, layouts, VMEM). The block pair
each test compiles is the one the guard's preflight accepts for that
call on a TPU backend, so the preflight's legality and VMEM model are
held to the real compiler. Nothing runs: these say nothing about
results or speed.

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


def _blocks(kernel, rows, cols, k=None, block_rows=128, block_cols=512):
    return preflight(
        kernel, rows=rows, cols=cols, d=D, block_rows=block_rows,
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
