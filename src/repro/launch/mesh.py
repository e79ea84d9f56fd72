"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init — the dry-run
sets XLA_FLAGS before any import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model stack places arrays with sharding constraints,
    # not with explicitly typed shardings.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(data: int, model: int, pods: int = 1):
    """Arbitrary mesh (hillclimb experiments re-balance data↔model here)."""
    if pods > 1:
        return _mesh((pods, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
