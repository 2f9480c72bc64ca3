"""Production meshes.

Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod: 2 x 16 x 16 = 512 chips ("pod", "data", "model") — the
"pod" axis is pure DP; the only cross-pod collective in training is
the gradient all-reduce (DCN-friendly).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests, examples)."""
    n = jax.device_count()
    if data * model > n:
        data, model = n, 1
    return _mesh((data, model), ("data", "model"))
