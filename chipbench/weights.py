"""Seeded random weights, made by the benchmark and handed to both sides.

``make_weights`` draws every weight of a configuration from the seed in
one jitted call on the device, in float32 (the type the program keeps its
parameters in), in the benchmark's own layout: the shapes and fan-in table
of the configuration's family (``families/<family>.py``), whose
``program_params`` hands the same arrays to the program in the tree its
model expects.  The reference reads the benchmark's layout, so it takes
nothing the program made.

The program pads its vocabulary (``pad_vocab``); the padded rows of the
embedding and columns of the head are zero, so a padded id's logit is 0.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def _draw(path, shape, key, vocab, fan_in_axes):
    name = path[-1]
    if name == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    w = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        return w.at[vocab:].set(0.0)
    fan_in = 1
    for ax in fan_in_axes[name]:
        fan_in *= shape[ax]
    w = w * fan_in ** -0.5
    return w.at[:, vocab:].set(0.0) if name == "head" else w


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make(key, shapes_items, vocab, treedef, fan_in_items):
    keys = jax.random.split(key, len(shapes_items))
    fan_in = dict(fan_in_items)
    leaves = [_draw(path, shape, k, vocab, fan_in)
              for (path, shape), k in zip(shapes_items, keys)]
    return jax.tree.unflatten(treedef, leaves)


def make_weights(shapes: Dict, fan_in: Dict[str, Tuple[int, ...]], vocab: int,
                 seed32: int) -> Dict:
    """Every weight of the layout ``shapes`` from ``seed32`` in one jitted
    call on the default device.  ``fan_in`` gives each matrix's contracted
    axes (std 1/sqrt(fan_in)); ``vocab`` is the published vocabulary, past
    which the embedding's rows and the head's columns are zero."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, int) for i in x))
    items = tuple((tuple(p.key for p in path), s) for path, s in flat)
    return _make(jax.random.key(seed32), items, vocab, treedef, tuple(sorted(fan_in.items())))
