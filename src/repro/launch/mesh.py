"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The single-pod mesh is 16x16 = 256 chips
(data, model); the multi-pod mesh is 2x16x16 = 512 chips (pod, data, model)
where "pod" is an additional data-parallel axis whose collectives cross the
inter-pod (DCN-class) links.  Axes are ``Auto``: the model code places
tensors through sharding constraints, not through sharding-in-types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh for CPU smoke usage (axes present but size 1)."""
    return jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
