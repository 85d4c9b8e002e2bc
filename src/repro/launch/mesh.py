"""Production mesh construction (required API per the assignment).

A function, not a module-level constant, so importing this module never
touches jax device state. Single-pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """General helper with explicit Auto axis types (elastic/test meshes).
    ``devices`` pins the mesh to those devices (default: the first
    prod(shape) visible ones)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)
