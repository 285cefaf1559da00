"""Production meshes.

Kept as functions (never module-level constants) so importing this module
does not touch jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
init, smoke tests see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    data = min(data, n)
    return _make_mesh(
        (data, max(1, min(model, n // data))), ("data", "model")
    )


def mesh_device_count(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
