"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model") — TPU v5e pod.
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries pure data parallelism across the DCN/ICI boundary;
FSDP stays inside a pod ("data"), tensor/expert parallelism inside a
16-chip ring ("model").

This is a FUNCTION (not a module-level constant) so importing never touches
jax device state — the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (sharding left
    to the compiler), the mode all of this repository's code is written for."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU integration tests (8 forced host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))
