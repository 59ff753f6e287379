"""The benchmark's own weights: made from the seed, handed to the program
in its own tree, padded vocabulary zeroed; and, for the tiny tree's
configuration and seed, the weights and reference logits pinned to what
the harness gave before its model-specific code moved into
``families/dense.py``."""
import dataclasses
import hashlib
import json

import jax
import numpy as np
import pytest

import tinytree
from chipbench import reference, traffic, weights
from chipbench.families import dense

SPEC = {"layers": 2, "d_model": 64, "heads": 4, "kv_heads": 2, "head_dim": 16, "d_ff": 96,
        "vocab": 500, "norm": "layernorm"}

#: sha256 of every weight leaf of the tiny configuration at seed 2**31 + 11
#: (stream 1, as ``run_cell`` draws them), padded vocabulary 2048
PINNED_LEAVES = {
    "['embed']": "8ee50db8f00ba44f2335fab2d449a9431a4894db91144f8ef26fd7fd74ff58da",
    "['final_norm']['bias']": "e8df39e14f646ddcbb0328c2a34b963fa2a14b2b2d3cbb7e9a72a62f6f9c70c1",
    "['final_norm']['scale']": "8012cbafcca9460bf1487e971c57e1f30091b34783ac7d27d54024a6c10f3975",
    "['head']": "aec40dea104dbf02ef8fe70f09c139be7f0a154f0fb69cdfb890a3156eb7d554",
    "['layers']['norm1']['bias']": "10b6e5f73dd2c56be58269f528445a02ac5aadd7cf3b4bd5a8cfceae2761b623",
    "['layers']['norm1']['scale']": "b34da38f4afc5a33ae1680585f4587083f3755ed4e3af45f3045c03e9faee985",
    "['layers']['norm2']['bias']": "516b4d17b0b2e4ec82fdd0a70e6c90696b09e8d3fec7adedd1c2669e9840d8fc",
    "['layers']['norm2']['scale']": "932499d404b6fd51ced37be61d00abbe64e5003fc43f19d6d85cf9343afe54e6",
    "['layers']['w_down']": "5137c4a17c4f32798c5574eea74cf23aeda1157a7458085b0907f16a119dbca7",
    "['layers']['w_gate']": "92f462fa03856d90d28a2483055b986ad104327b8b027e7e3850d4acc32f35fa",
    "['layers']['w_up']": "b14b7de93bb2ba17ecbbae9fc1ee96daff8ed0954fee4536abd92c855ae687cb",
    "['layers']['wk']": "b3ea2947b17fe0c184e5f99ddafa6ed4ded8120fbc491b772b93735b864a0fad",
    "['layers']['wo']": "129421a5a9a79474db420cce15bd1b99c5dd91254dd76d76b0fc718951398e90",
    "['layers']['wq']": "a3c1e6f2f2b8f00ee201c9ddc34e5cac99414d1547cb67aa1c4f8f102a6c5209",
    "['layers']['wv']": "2f77cdae14e594e305787df56e6f1721d173a043ac88e79abe700c563e3fc007",
}

#: sha256 of the reference logits (10, 512) of one 40-token prompt at
#: positions 30..39, bucket 64, and their first column at every third
#: position, for the reference and for the fp8 control
PINNED_LOGITS = {
    False: ("af201d9f9abb01d038cb91c1d302cb9a79ad3ac8535527803e9bf77f5bcf42ab",
            [0.5724533796310425, 0.11897033452987671, -1.1966259479522705,
             -0.04691420868039131]),
    True: ("9efac05ef15ce019a5f393aaba71b686e57680257ba6bc112446e2105c9e6091",
           [0.6151461601257324, 0.15560418367385864, -1.0196912288665771,
            -0.029537804424762726]),
}


def make(spec, padded_vocab, seed32):
    return weights.make_weights(dense.weight_shapes(spec, padded_vocab), dense.FAN_IN,
                                spec["vocab"], seed32)


def model(**over):
    from repro.configs.registry import get_config
    from repro.models.transformer import LM

    cfg = dataclasses.replace(get_config("stablelm_1_6b"), **{
        **tinytree.TINY, "d_model": 64, "head_dim": 16, "d_ff": 96, "vocab_size": 500, **over})
    return LM(cfg, attn_impl="naive", remat=None)


def sha(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


def test_same_seed_same_weights_and_zero_padding():
    a, b, c = (make(SPEC, 2048, s) for s in (7, 7, 8))
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["head"], c["head"])
    assert not np.asarray(a["embed"][500:]).any() and not np.asarray(a["head"][:, 500:]).any()
    assert np.asarray(a["embed"][:500]).std() > 0.5


def test_program_tree_is_checked_against_the_model():
    w = make(SPEC, 2048, 1)
    tree = dense.program_params(w, model())
    assert tree["layers"]["attn"]["wq"] is w["layers"]["wq"]
    with pytest.raises(ValueError, match="parameter tree"):
        dense.program_params(w, model(d_ff=128))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tinytree.build(tmp_path_factory.mktemp("tiny"), 0.05)
    spec = reference.spec_of(json.loads((root / "bench" / "configs" / "tiny.json").read_text()))
    return spec, make(spec, 2048, traffic.seed_state(2**31 + 11, 1))


def test_weights_are_pinned_leaf_by_leaf(tiny):
    _, w = tiny
    got = {jax.tree_util.keystr(p): sha(a) for p, a in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert list(got) == list(PINNED_LEAVES)
    assert got == PINNED_LEAVES


@pytest.mark.parametrize("fp8", [False, True])
def test_reference_logits_are_pinned(tiny, fp8):
    spec, w = tiny
    ids = [(3 + 7 * i) % spec["vocab"] for i in range(40)]
    lg = np.asarray(dense.forward_logits(w, spec, ids, 30, 10, 64, fp8=fp8))
    digest, column = PINNED_LOGITS[fp8]
    assert lg.shape == (10, 512)
    assert lg[::3, 0].tolist() == column
    assert sha(lg) == digest
