"""The engine's one cast of the weight matrices to the compute dtype: the
same tokens and logits as from f32 weights, which leaves it casts, the
caller's arrays left whole, its counter, and the rule that decides."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.launch.serve import pool_config
from repro.models.transformer import LM
from repro.serve import engine as engine_mod
from repro.serve.engine import COMPUTE_DTYPE_LEAVES, Request, ServeEngine, cast_fits
from repro.serve.paged_runner import paged_decode_step_jit

ARCHS = ["stablelm_1_6b", "zamba2_7b"]      # a dense and a hybrid model

#: a device whose free bytes no copy fits in
FULL = {"bytes_limit": 16 * 2**30, "bytes_in_use": 16 * 2**30 - 1}


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    cfg = dataclasses.replace(get_config(request.param).smoke(), dtype="bfloat16")
    model = LM(cfg, attn_impl="naive", remat=None)
    return model, model.init(jax.random.key(0))


def _engine(served, monkeypatch, full: bool):
    model, params = served
    if full:
        monkeypatch.setattr(engine_mod, "_memory_stats", lambda device: FULL)
    return ServeEngine(model, params, pool_config(model.cfg, max_seqs=4))


def _prompts(cfg):
    rng = np.random.default_rng(17)
    return [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 12, 9)]


def _is_matrix(path) -> bool:
    return path[-1].key in COMPUTE_DTYPE_LEAVES


def test_cast_engine_serves_the_tokens_of_f32_weights(served, monkeypatch):
    outs = []
    for full in (False, True):
        eng = _engine(served, monkeypatch, full)
        for i, p in enumerate(_prompts(eng.cfg)):
            eng.submit(Request(rid=i, prompt=p, max_new=6))
        outs.append([r.out for r in sorted(eng.run(), key=lambda r: r.rid)])
    assert outs[0] == outs[1]


def test_paged_step_logits_are_bit_identical(served, monkeypatch):
    model, params = served
    eng = _engine(served, monkeypatch, full=False)
    for i, p in enumerate(_prompts(eng.cfg)):
        eng.submit(Request(rid=i, prompt=p, max_new=8))
    for _ in range(3):
        eng.step()
    slots = sorted(eng.live)
    lens = eng.pool.seq_lens()[slots]
    args = (jnp.asarray([[eng.live[s].out[-1]] for s in slots], jnp.int32),
            jnp.asarray(lens[:, None] - 1, jnp.int32), eng.pool.k, eng.pool.v,
            jnp.asarray(eng.pool.block_table()[slots]), jnp.asarray(lens))
    state = (() if eng.pool.conv is None else
             ((eng.pool.conv, eng.pool.ssd, jnp.asarray(slots, jnp.int32)),))
    cast = paged_decode_step_jit(eng.params, model.cfg, *args, *state, use_kernel=False)
    f32 = paged_decode_step_jit(params, model.cfg, *args, *state, use_kernel=False)
    assert cast[0].dtype == f32[0].dtype
    for a, b in zip(cast, f32):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_engine_casts_exactly_the_matrices_and_leaves_the_callers_arrays(
        served, monkeypatch):
    model, params = served
    eng = _engine(served, monkeypatch, full=False)
    held = jax.tree_util.tree_flatten_with_path(eng.params)[0]
    given = jax.tree.leaves(params)
    assert any(_is_matrix(path) for path, _ in held)
    assert any(not _is_matrix(path) for path, _ in held)
    for (path, a), b in zip(held, given):
        assert a.dtype == (jnp.bfloat16 if _is_matrix(path) else jnp.float32), path
        assert (a is b) != _is_matrix(path), path
        assert b.dtype == jnp.float32 and not b.is_deleted(), path
    kept = _engine(served, monkeypatch, full=True)
    assert kept.params is params


def test_weights_in_compute_dtype_counter(served, monkeypatch):
    assert _engine(served, monkeypatch, full=False).metrics()["weights_in_compute_dtype"] == 1.0
    assert _engine(served, monkeypatch, full=True).metrics()["weights_in_compute_dtype"] == 0.0


def test_weights_already_in_the_compute_dtype_are_not_copied(served, monkeypatch):
    model, _ = served
    cfg = dataclasses.replace(model.cfg, dtype="float32")
    f32_model = LM(cfg, attn_impl="naive", remat=None)
    params = f32_model.init(jax.random.key(0))
    eng = ServeEngine(f32_model, params, pool_config(cfg, max_seqs=4))
    assert eng.params is params
    assert eng.metrics()["weights_in_compute_dtype"] == 1.0


GB = 10**9
LIMIT = 16_911_433_728            # what a v5e gives XLA: 15.75 GiB
KV = 1_711_276_032                # zamba2's and stablelm-longprompt's K (or V) pool


@pytest.mark.parametrize("needed, resident, cast", [
    # the bf16 copy of the matrices; f32 weights + K and V pools (+ state slots)
    (3.29 * GB, 6.58 * GB + 2 * KV, True),                 # stablelm-longprompt
    (3.92 * GB, 7.83 * GB + 2 * 124_780_544, True),       # chatglm3-longprompt
    (3.29 * GB, 6.58 * GB + 2 * 805_306_368, True),       # stablelm-shortprompt
    (4.49 * GB, 8.98 * GB + 2 * KV + 554_139_648, False),  # zamba2-longprompt
], ids=["stablelm-longprompt", "chatglm3-longprompt", "stablelm-shortprompt",
        "zamba2-longprompt"])
def test_cast_decision_at_the_benchmark_cells(needed, resident, cast):
    assert cast_fits(int(needed), {"bytes_limit": LIMIT, "bytes_in_use": int(resident)}) is cast


def test_a_device_without_memory_stats_has_room():
    assert cast_fits(10**15, None)
    assert cast_fits(10**15, {})
