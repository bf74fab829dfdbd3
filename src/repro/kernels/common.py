"""Helpers shared by the Pallas kernels, their ``pallas_call`` wrappers
and the ``ref.py`` oracles: the in-kernel matmul, block padding, and
the varying-manual-axes (``vma``) typing that ``jax.shard_map`` checks.

Under ``jax.shard_map`` every value carries the set of mesh axes it
varies over. A kernel's outputs must declare it (:func:`sds`), a fresh
buffer or scan carry must be cast to it (:func:`vary_as`), and a
cotangent computed from operands of different axes must be summed back
to its primal's axes (:func:`psum_to`). Outside ``shard_map`` all three
are no-ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dot(a, b):
    """In-kernel matmul with a float32 result. Float32 operands are
    contracted at full float32 precision: Mosaic's default is one
    bfloat16 MXU pass (~2e-3 relative error on a TPU v5e), which would
    break the kernels' agreement with their float32 references.
    bfloat16 operands take a single pass either way."""
    wide = jnp.float32 in (a.dtype, b.dtype)
    return jnp.dot(
        a, b, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if wide else None,
    )


def pad_to(arr, axis, multiple, value=0):
    """Pad ``arr`` along ``axis`` up to a multiple of ``multiple``."""
    pad = (-arr.shape[axis]) % multiple
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths, constant_values=value)


def vma(*operands) -> frozenset:
    """Union of the operands' varying-manual-axes sets (empty outside
    ``jax.shard_map``)."""
    axes = frozenset()
    for op in operands:
        try:
            axes = axes | jax.typeof(op).vma
        except (AttributeError, TypeError):
            pass
    return axes


def sds(shape, dtype, *operands):
    """ShapeDtypeStruct with the union of the operands' ``vma`` (a
    ``pallas_call`` under ``jax.shard_map`` needs it)."""
    axes = vma(*operands)
    if axes:
        return jax.ShapeDtypeStruct(shape, dtype, vma=axes)
    return jax.ShapeDtypeStruct(shape, dtype)


def vary_as(tree, *operands):
    """Cast every leaf of ``tree`` to vary over each manual axis the
    operands vary over.

    A ``lax.scan`` carry must enter with the type its body gives it, and
    an ``input_output_aliases`` output takes its aliased input's type: a
    zeros buffer that accumulates per-device partials must carry the
    kernel's full ``vma``, or the partials are typed as replicated and
    never summed."""
    want = vma(*operands)

    def cast(leaf):
        extra = tuple(sorted(want - vma(leaf)))
        return jax.lax.pcast(leaf, extra, to="varying") if extra else leaf

    return jax.tree.map(cast, tree) if want else tree


def psum_to(ct, primal):
    """Sum a kernel-computed cotangent over the manual axes it varies
    over but its primal does not. Under ``jax.shard_map`` a backward
    kernel mixes operands that vary over different axes (positions over
    ``data``, the catalog over ``model``), so each device holds a
    partial gradient; the psum is the transpose of the implicit
    broadcast, and gives the cotangent its primal's type as
    ``custom_vjp`` requires."""
    extra = tuple(sorted(vma(ct) - vma(primal)))
    return jax.lax.psum(ct, extra) if extra else ct
