"""Declarative parameter trees with logical sharding axes.

A module is a (nested) dict of :class:`ParamDef`; ``init_params`` turns it
into a pytree of arrays.  Each definition also names a logical axis per
dimension; no code maps those names onto a mesh.  This keeps model code
framework-free (pure functions over dicts).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "ParamDef",
    "init_params",
    "count_params",
]


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis name per dim (None = replicated)
    init: str = "normal"              # normal | zeros | ones | embed | scaled
    scale: float = 1.0
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_leaf(key: jax.Array, d: ParamDef) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "embed":
        return jax.random.normal(key, d.shape, d.dtype) * d.scale
    # fan-in scaled normal (He/LeCun-ish): last-but-one axis is fan-in for
    # (in, out) matrices; fall back to first dim.
    fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[0]
    std = d.scale / math.sqrt(max(fan_in, 1))
    return jax.random.normal(key, d.shape, d.dtype) * std


def init_params(key: jax.Array, defs: Any) -> Any:
    """Materialize a pytree of ParamDef into arrays with per-leaf PRNG keys."""
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    keys = jax.random.split(key, len(leaves))
    out = [_init_leaf(k, d) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)


def count_params(tree: Any) -> int:
    leaves = jax.tree.leaves(tree)
    return sum(
        int(math.prod(x.shape)) if hasattr(x, "shape") else 0 for x in leaves
    )
