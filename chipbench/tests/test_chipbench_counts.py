"""Operation and byte counts of the dense family, and the chip's peaks,
against numbers worked out by hand."""
import json
from pathlib import Path

import pytest

from chipbench import counts, reference
from chipbench.families import dense

BENCH = Path(__file__).resolve().parents[1]


def spec(name):
    return reference.spec_of(json.loads((BENCH / "configs" / f"{name}.json").read_text()))


def test_paged_attention_stablelm_by_hand():
    # MHA 32 heads x 64: per cached token QK and PV are 2*32*64 FLOPs each,
    # and K plus V is 2*32*64*2 B = 8192 B at bf16; q and out 2*32*64*2 B a row
    c = dense.paged_attention(spec("stablelm_1_6b"), [1024, 2048])
    assert c["flops"] == 4 * 32 * 64 * 3072 == 25165824
    assert c["bytes"] == 8192 * 3072 + 2 * 8192 == 25182208


def test_paged_attention_chatglm3_by_hand():
    # GQA 32 query heads, 2 KV heads of 128: K plus V is 2*2*128*2 B = 1024 B a token
    c = dense.paged_attention(spec("chatglm3_6b_7layers"), [100])
    assert c["flops"] == 4 * 32 * 128 * 100 == 1638400
    assert c["bytes"] == 1024 * 100 + 2 * 32 * 128 * 2 == 118784


def test_paged_attention_over_the_model_is_every_layer():
    # dense: every layer is an attention layer; stablelm 24, chatglm3 7
    for name, layers in (("stablelm_1_6b", 24), ("chatglm3_6b_7layers", 7)):
        s = spec(name)
        one, model = dense.paged_attention(s, [100, 7]), dense.paged_attention_model(s, [100, 7])
        assert model == {k: layers * v for k, v in one.items()}


def test_model_flops_stablelm_by_hand():
    s = spec("stablelm_1_6b")
    # per layer: q, o 2048*2048 each; k, v 2048*2048 each (MHA); 3 * 2048 * 5632
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert dense.matmul_params_per_layer(s) == per_layer == 51380224
    head = 2 * 2048 * 100352
    assert dense.decode_token_flops(s, 1) == 2 * 24 * per_layer + 4 * 24 * 2048 + head
    assert dense.prefill_flops(s, 4) == (2 * 24 * per_layer * 4 + 4 * 24 * 2048 * 10 + head)


def test_model_flops_chatglm3_by_hand():
    s = spec("chatglm3_6b_7layers")
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 13696
    assert dense.matmul_params_per_layer(s) == per_layer == 203948032
    assert dense.decode_token_flops(s, 10) == (2 * 7 * per_layer + 4 * 7 * 4096 * 10
                                                + 2 * 4096 * 65024)


def test_peaks_by_device_kind():
    p = counts.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    assert counts.roofline_seconds(197e12, 1.0, p) == 1.0
    assert counts.roofline_seconds(1.0, 819e9, p) == 1.0
