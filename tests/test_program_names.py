"""The benchmark finds each serving program's device time in a trace by the
program's name (``chipbench/metrics/*``), so a rename would leave its
per-layer metrics empty.  Each case lowers one program the engine dispatches,
on the CPU at a tiny size, and checks the name of its module."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import kv_pool
from repro.launch.serve import pool_config
from repro.models.transformer import LM
from repro.serve.engine import ServeEngine

B, S = 3, 40


@pytest.fixture(scope="module")
def engines():
    out = {}
    for kind, arch in (("dense", "stablelm_1_6b"), ("hybrid", "zamba2_7b")):
        cfg = get_config(arch).smoke()
        model = LM(cfg, attn_impl="naive", remat=None)
        out[kind] = ServeEngine(model, model.init(jax.random.key(0)),
                                pool_config(cfg, max_seqs=4))
    return out


def paged_step(eng):
    pool = eng.pool
    tbl = jnp.zeros((B, pool.cfg.max_blocks_per_seq), jnp.int32)
    state = (() if pool.conv is None else
             ((pool.conv, pool.ssd, jnp.arange(B, dtype=jnp.int32)),))
    tok = jnp.zeros((B, 1), jnp.int32)
    return eng._paged_step.lower(eng.params, eng.cfg, tok, tok, pool.k, pool.v, tbl,
                                 jnp.ones((B,), jnp.int32), *state,
                                 use_kernel=eng.use_kernel)


def prefill(eng):
    batch = {"tokens": jnp.zeros((1, S), jnp.int32),
             "positions": jnp.arange(S, dtype=jnp.int32)[None]}
    return eng._decode_step.lower(eng.params, batch,
                                  eng.model.init_cache(1, S, recent_size=S))


def write_tokens(eng):
    pool = eng.pool
    L, _, _, KV, hd = pool.k.shape
    kv = jnp.zeros((L, B, KV, hd), pool.k.dtype)
    at = np.zeros((B,), np.int32)
    return kv_pool._write_tokens.lower(pool.k, pool.v, at, at, kv, kv)


def write_pages(eng):
    pool = eng.pool
    L, _, _, KV, hd = pool.k.shape
    kv = jnp.zeros((L, 1, S, KV, hd), pool.k.dtype)
    runs = np.zeros((pool.blocks_for(S), 3), np.int32)
    return kv_pool._write_pages.lower(pool.k, pool.v, runs, np.int32(1), kv, kv)


def write_states(eng):
    pc = eng.pool.cfg
    conv = jnp.zeros((pc.state_layers, B, pc.conv_width), jnp.float32)
    ssd = jnp.zeros((pc.state_layers, B, pc.ssd_heads, pc.ssd_width), jnp.float32)
    return kv_pool._write_states.lower(eng.pool.conv, eng.pool.ssd,
                                       np.arange(B, dtype=np.int32), conv, ssd)


@pytest.mark.parametrize("kind, lower, name", [
    ("dense", paged_step, "jit_paged_decode_step"),
    ("hybrid", paged_step, "jit_paged_decode_step"),
    ("dense", prefill, "jit_decode_step"),
    ("dense", write_tokens, "jit__write_tokens"),
    ("dense", write_pages, "jit__write_pages"),
    ("hybrid", write_states, "jit__write_states"),
], ids=["paged_dense", "paged_hybrid", "prefill", "write_tokens", "write_pages",
        "write_states"])
def test_program_is_named_as_the_benchmark_reads_it(engines, kind, lower, name):
    text = lower(engines[kind]).as_text()
    assert re.match(r"module @(\S+)", text).group(1) == name
