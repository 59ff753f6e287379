"""Seeded random weights, made by the benchmark and handed to both sides.

``make_weights`` draws every weight of a configuration from the seed in
one jitted call on the device, in float32 (the type the program keeps its
parameters in), in the benchmark's own layout.  ``program_params`` hands
the same arrays to the program in the tree its model expects, checked leaf
by leaf against the tree ``LM.init`` would build; the reference reads the
benchmark's layout.  So the reference takes nothing the program made.

The program pads its vocabulary (``pad_vocab``); the padded rows of the
embedding and columns of the head are zero, so a padded id's logit is 0.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def weight_shapes(spec: Dict, padded_vocab: int) -> Dict:
    """The benchmark's weight layout: per-layer leaves stacked on a leading
    layer axis, the embedding and head over the padded vocabulary."""
    L, d, H, KV, hd, f = (spec[k] for k in
                          ("layers", "d_model", "heads", "kv_heads", "head_dim", "d_ff"))
    norm = {"scale": (d,), "bias": (d,)} if spec["norm"] == "layernorm" else {"scale": (d,)}
    stacked = lambda g: {k: (L,) + s for k, s in g.items()}  # noqa: E731
    return {
        "embed": (padded_vocab, d),
        "head": (d, padded_vocab),
        "final_norm": norm,
        "layers": {
            "norm1": stacked(norm), "norm2": stacked(norm),
            "wq": (L, d, H, hd), "wk": (L, d, KV, hd), "wv": (L, d, KV, hd),
            "wo": (L, H, hd, d),
            "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
        },
    }


#: fan-in of each matrix (std 1/sqrt(fan_in)): the axes its input contracts
_FAN_IN = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2), "w_gate": (1,),
           "w_up": (1,), "w_down": (1,), "head": (0,)}


def _draw(path, shape, key, vocab):
    name = path[-1]
    if name == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    w = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        return w.at[vocab:].set(0.0)
    fan_in = 1
    for ax in _FAN_IN[name]:
        fan_in *= shape[ax]
    w = w * fan_in ** -0.5
    return w.at[:, vocab:].set(0.0) if name == "head" else w


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, shapes_items, vocab, treedef):
    keys = jax.random.split(key, len(shapes_items))
    leaves = [_draw(path, shape, k, vocab) for (path, shape), k in zip(shapes_items, keys)]
    return jax.tree.unflatten(treedef, leaves)


def make_weights(spec: Dict, padded_vocab: int, seed32: int) -> Dict:
    """Every weight from ``seed32`` in one jitted call on the default device."""
    shapes = weight_shapes(spec, padded_vocab)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, int) for i in x))
    items = tuple((tuple(p.key for p in path), s) for path, s in flat)
    return _make(jax.random.key(seed32), items, spec["vocab"], treedef)


def program_params(w: Dict, model) -> Dict:
    """The program's parameter tree over the same device arrays, checked
    against the tree ``model.init`` would build (structure, shapes, dtypes)."""
    lw = w["layers"]
    tree = {
        "embed": {"tok": w["embed"], "head": w["head"]},
        "final_ln": dict(w["final_norm"]),
        "layers": {
            "ln1": dict(lw["norm1"]), "ln2": dict(lw["norm2"]),
            "attn": {"wq": lw["wq"], "wk": lw["wk"], "wv": lw["wv"], "wo": lw["wo"]},
            "mlp": {"wg": lw["w_gate"], "wu": lw["w_up"], "wo": lw["w_down"]},
        },
    }
    want = jax.eval_shape(model.init, jax.random.key(0))
    sig = lambda t: [(a.shape, a.dtype) for a in jax.tree.leaves(t)]  # noqa: E731
    if jax.tree.structure(want) != jax.tree.structure(tree) or sig(want) != sig(tree):
        raise ValueError(f"weights do not fit the program's parameter tree:\n{sig(want)}\n"
                         f"vs\n{sig(tree)}")
    return tree
