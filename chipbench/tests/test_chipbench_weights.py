"""The benchmark's own weights: made from the seed, handed to the program
in its own tree, padded vocabulary zeroed."""
import dataclasses

import jax
import numpy as np
import pytest

import tinytree
from chipbench import weights

SPEC = {"layers": 2, "d_model": 64, "heads": 4, "kv_heads": 2, "head_dim": 16, "d_ff": 96,
        "vocab": 500, "norm": "layernorm"}


def model(**over):
    from repro.configs.registry import get_config
    from repro.models.transformer import LM

    cfg = dataclasses.replace(get_config("stablelm_1_6b"), **{
        **tinytree.TINY, "d_model": 64, "head_dim": 16, "d_ff": 96, "vocab_size": 500, **over})
    return LM(cfg, attn_impl="naive", remat=None)


def test_same_seed_same_weights_and_zero_padding():
    a, b, c = (weights.make_weights(SPEC, 2048, s) for s in (7, 7, 8))
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["head"], c["head"])
    assert not np.asarray(a["embed"][500:]).any() and not np.asarray(a["head"][:, 500:]).any()
    assert np.asarray(a["embed"][:500]).std() > 0.5


def test_program_tree_is_checked_against_the_model():
    w = weights.make_weights(SPEC, 2048, 1)
    tree = weights.program_params(w, model())
    assert tree["layers"]["attn"]["wq"] is w["layers"]["wq"]
    with pytest.raises(ValueError, match="parameter tree"):
        weights.program_params(w, model(d_ff=128))
