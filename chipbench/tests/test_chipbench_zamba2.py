"""The zamba2 family: its counts pinned at the published widths, its
program_config refusing any departure, and a tiny tree through the harness
on the CPU, which ends ``correct`` and turns ``correct`` false when the fp8
control takes the program's place or a fault is planted in the timed path."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import tinyzamba
from chipbench import reference, run
from chipbench.families import zamba2

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "zamba2_7b_18layers.json").read_text())
SPEC = reference.spec_of(CFG)

#: tiny limit, from CPU readings over four seeds (every served request
#: compared): program widest gap at most 8.10, fp8 control at least 31.5.
#: The gaps are wide for the logits' scale (std about 8 at width 64): a bf16
#: reference alone reads up to 7.2, as random layers amplify rounding
TINY_LIMIT = 16.0
SEED = 2**31 + 11


def test_layer_counts_at_published_widths():
    # Mamba2 layer: in_proj 3584 x (7168 + 7424 + 112), conv 4 x 7424 plus
    # bias, dt_bias/A_log/D 3 x 112, gated norm 7168, out_proj 7168 x 3584,
    # input norm 3584
    assert zamba2.mamba_params(SPEC) == 78_437_456
    # shared block: norm 7168, q/k/v 7168 x 7168 each, o 7168 x 3584,
    # pre-MLP norm 3584, gate_up 3584 x 28672, down 14336 x 3584
    assert zamba2.shared_block_params(SPEC) == 333_982_208
    # one use: LoRA 3584 x 128 and 128 x 28672, linear 3584 x 3584
    assert zamba2.use_params(SPEC) == 16_973_824
    # 3 uses x (K, V) x 32 heads x 224 x 2 B
    assert zamba2.kv_bytes_per_token(SPEC) == 86_016


def test_model_total_is_the_programs():
    from repro.models.params import count_params
    from repro.models.transformer import LM

    pcfg = zamba2.program_config(CFG, SPEC)
    total = (18 * 78_437_456 + 2 * 333_982_208 + 3 * 16_973_824
             + 32000 * 3584 + 3584)
    assert total == 2_245_451_680
    assert pcfg.n_params() == total
    padded = (32768 - 32000) * 3584       # the program pads its vocabulary
    assert count_params(jax.eval_shape(LM(pcfg).init, jax.random.key(0))) == total + padded


def test_paged_attention_counts_by_hand():
    # 3 uses; MHA 32 x 224: QK and PV 2*32*224 FLOPs each per token; K + V
    # 2*32*224*2 B a token, q in and out 2*32*224*2 B a sequence
    c = zamba2.paged_attention_model(SPEC, [1024, 2048])
    assert c["flops"] == 3 * 4 * 32 * 224 * 3072
    assert c["bytes"] == 3 * (2 * 32 * 224 * 2 * 3072 + 2 * 2 * 32 * 224 * 2)


def test_model_flops_by_hand():
    d, f = 3584, 14336
    mamba = d * 14704 + 7168 * d
    block = 2 * d * 3 * 32 * 224 + 32 * 224 * d + 3 * d * f
    per_token = 18 * mamba + 3 * (block + 16_973_824)
    assert zamba2.matmul_params_per_token(SPEC) == per_token
    ssd = 5 * 18 * 112 * 64 * 64
    head = 2 * d * 32000
    assert zamba2.decode_token_flops(SPEC, 10) == 2 * per_token + 3 * 4 * 32 * 224 * 10 + ssd + head
    assert zamba2.prefill_flops(SPEC, 4) == ((2 * per_token + ssd) * 4
                                             + 3 * 4 * 32 * 224 * 10 + head)


@pytest.mark.parametrize("key, value", [
    ("hybrid_layer_ids", [6, 12, 17]), ("mem_blocks", 1), ("adapter_rank", 64),
    ("head_dim", 112), ("ssm_groups", 1), ("conv_bias", False), ("norm_eps", 1e-6),
    ("tie_head", False), ("rotary", "interleaved"), ("activation", "silu"),
])
def test_program_config_exits_naming_the_departure(key, value):
    with pytest.raises(SystemExit) as e:
        zamba2.program_config(CFG, dict(SPEC, **{key: value}))
    assert repr(key) in str(e.value)


def test_program_config_refuses_a_program_without_the_published_block(monkeypatch):
    from repro.configs import registry

    old = dataclasses.replace(registry.get_config("zamba2_7b"), hybrid_layer_ids=(),
                              num_mem_blocks=0)
    monkeypatch.setattr(registry, "get_config", lambda name: old)
    with pytest.raises(SystemExit) as e:
        zamba2.program_config(CFG, SPEC)
    assert "hybrid_layer_ids" in str(e.value) and "mem_blocks" in str(e.value)


def run_tiny(tmp_path, **kw):
    root = tinyzamba.build(tmp_path, TINY_LIMIT)
    return run.run_cell(root, tinyzamba.CELL, SEED, 1.0, False, require_chip=False, **kw)


def test_tiny_zamba2_through_the_harness_is_correct(tmp_path, no_compile_cache):
    r = run_tiny(tmp_path)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["checks"]["tokens_compared"]["value"] >= 16


def test_fp8_control_fails_where_the_program_passes(tmp_path, no_compile_cache):
    r = run_tiny(tmp_path, control=True)
    assert not r["correct"]
    assert r["readings"]["control_gap_max"] > TINY_LIMIT >= r["readings"]["program_gap_max"]


def _planted(fault, monkeypatch):
    """Plant ``fault`` in the program the harness is about to build."""
    import repro.models.transformer as TF
    import repro.serve.engine as engine
    from repro.core.kv_pool import PagedKVPool

    if fault == "state_write_dropped":
        monkeypatch.setattr(PagedKVPool, "write_token_states", lambda *a, **k: None)
        return
    if fault == "concat_left_out":
        keep = TF.shared_block_input
        monkeypatch.setattr(TF, "shared_block_input",
                            lambda sp, cfg, x, e: keep(sp, cfg, x, jnp.zeros_like(e)))
        return

    class Engine(engine.ServeEngine):
        def __init__(self, model, params, pool_cfg, **kw):
            params = dict(params)
            if fault == "blocks_swapped":
                params["shared"] = jax.tree.map(lambda a: a[::-1], params["shared"])
            else:                                 # wrong use's adapter
                params["uses"] = dict(params["uses"], **{
                    k: jnp.roll(params["uses"][k], 1, axis=0) for k in ("lora_a", "lora_b")})
            super().__init__(model, params, pool_cfg, **kw)

    monkeypatch.setattr(engine, "ServeEngine", Engine)


@pytest.mark.parametrize("fault", ["state_write_dropped", "blocks_swapped", "concat_left_out",
                                   "adapter_of_another_use"])
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, no_compile_cache, fault):
    jax.clear_caches()                # no step traced without the fault is reused
    _planted(fault, monkeypatch)
    try:
        r = run_tiny(tmp_path)
    finally:
        jax.clear_caches()
    assert not r["correct"]
    assert r["checks"]["logit_gap_max"]["value"] > TINY_LIMIT
