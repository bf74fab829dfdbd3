"""Production meshes (DESIGN.md §4).

``make_production_mesh`` is a FUNCTION (not a module constant) so
importing this module never touches jax device state — the dry-run must
set ``XLA_FLAGS`` before the first device query.

Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods × 256 as
(pod=2, data=16, model=16); the ``pod`` axis carries only the cross-pod
slice of gradient reductions (DCI), everything bandwidth-hungry stays on
the in-pod ICI axes. The same axis names scale to 1000+ nodes by growing
``pod`` — no code changes, only the mesh shape.

Axis names live in ``repro.dist``; this module only chooses shapes.
Every axis is ``AxisType.Auto``: layouts are stated by the specs in
``repro.dist.sharding`` and partitioned by GSPMD or ``shard_map``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model: int = 1, max_data: int = 0):
    """Small mesh over whatever devices exist — CPU tests and examples.

    ``max_data`` > 0 caps the data axis to the largest size that divides
    it (e.g. the global batch), so smoke-scale batches still shard
    evenly when the host exposes many (virtual) devices; surplus devices
    are simply left out of the mesh.
    """
    n = len(jax.devices())
    model = min(model, n)
    data = n // model
    if max_data > 0:
        while data > 1 and max_data % data != 0:
            data -= 1
    return make_mesh((data, model), ("data", "model"))


def dp_size(mesh) -> int:
    size = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            size *= mesh.shape[ax]
    return size
