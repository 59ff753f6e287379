"""Production mesh construction.

One pod: (16, 16) = 256 chips, axes ("data", "model") — a TPU v5e pod.
FSDP runs over "data", tensor/expert parallelism inside a 16-chip ring
("model").

These are FUNCTIONS (not module-level constants) so importing never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (sharding left
    to the compiler), the mode all of this repository's code is written for."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh():
    return make_mesh((16, 16), ("data", "model"))


def make_smoke_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU integration tests (8 forced host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))
