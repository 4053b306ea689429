"""Production meshes.  Functions, never module-level constants — importing
this module must not touch jax device state.
"""
from __future__ import annotations

import jax


def production_mesh_shape(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data", "model").
    Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model").
    Returns (axis sizes, axis names)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return shape, axes


def make_production_mesh(*, multi_pod: bool = False):
    """The production mesh over real (or placeholder) devices."""
    shape, axes = production_mesh_shape(multi_pod=multi_pod)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh helper for tests/examples."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist right now, as a 1-D ("data",) mesh."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
