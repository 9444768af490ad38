"""Production mesh construction (assignment §MULTI-POD DRY-RUN item 1).

A FUNCTION, not a module constant: importing this module never touches jax
device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_local_mesh", "make_mesh", "make_production_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (sharding follows the
    logical-axis rules, not explicit per-op annotations)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (CPU demos/tests)."""
    return make_mesh((1, 1), ("data", "model"))
