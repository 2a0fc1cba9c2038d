"""Production mesh construction (assignment-mandated shapes).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU multi-device tests (requires forced host devices)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
