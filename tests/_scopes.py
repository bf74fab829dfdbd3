"""Reading the named scopes of a compiled train step's HLO text, with
the benchmark's own reading of a scope path (``bench/harness/scopes.py``),
the one a profile is reduced by."""
import os
import re
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
from harness.scopes import scope_of as scope  # noqa: E402

SCOPES = ("backbone", "select", "gather_loss", "optim")

_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.+?) "
    r"(custom-call|dot|while|fusion)\(([^)]*)\)(.*)$"
)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instructions(text):
    """``(name, opcode, shape, operands, op_name)`` of every custom-call,
    dot, while and fusion in a compiled module's ``as_text()``."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            name, shape, opcode, operands, rest = m.groups()
            on = _OP_NAME.search(rest)
            out.append((name, opcode, shape, operands,
                        on.group(1) if on else None))
    return out


def unscoped(text):
    """The instructions under none of ``SCOPES``, leaving out what no
    scope can own: instructions the compiler made (no ``op_name``), the
    step key's random bits (``threefry``, ``u32`` values) and constants
    (no operand but constants)."""
    bad = []
    for name, opcode, shape, operands, op_name in instructions(text):
        if op_name is None or "threefry" in op_name:
            continue
        if shape.startswith("u32[") or all(
                o.strip().startswith("%constant") for o in operands.split(",")
                if o.strip()):
            continue
        if scope(op_name).split("/")[0] not in SCOPES:
            bad.append((name, opcode, op_name))
    return bad


def seqrec_step_text(batch=4, seq_len=16, compile_for=None):
    """Compiled HLO text of the sasrec-sce smoke train step (catalog of
    500 items), compiled for the CPU or, with ``compile_for`` a mesh over
    described devices, for that mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import get_arch
    from repro.dist.sharding import batch_spec
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import _init_params, _setup

    arch = get_arch("sasrec-sce")
    cfg, shape, _ = _setup(arch, batch, seq_len, None)
    mesh = compile_for or make_host_mesh(max_data=batch)
    step, (opt_init, _), _ = steps_lib.make_seqrec_train_step(
        arch, cfg, mesh, shape, sce_mode="exact")
    rep = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, batch_spec(mesh, 2))

    def sds(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    params = jax.eval_shape(lambda: _init_params(arch, cfg,
                                                 jax.random.PRNGKey(0)))
    opt = jax.eval_shape(opt_init, params)
    l = cfg.max_len
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((batch, l), jnp.int32, sharding=rows),
        "targets": jax.ShapeDtypeStruct((batch, l), jnp.int32,
                                        sharding=rows),
        "valid": jax.ShapeDtypeStruct((batch, l), jnp.bool_, sharding=rows),
        "loss_cap": jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
    }
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    with jax.set_mesh(mesh):
        return jax.jit(step).lower(sds(params, rep), sds(opt, rep),
                                   batch_abs, key).compile().as_text()
