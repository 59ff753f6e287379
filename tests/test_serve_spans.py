"""Names that put the serving path on the profiler's clock: the paged decode
step's ``jax.named_scope`` parts reach the compiled program's metadata."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.models.transformer import LM
from repro.serve.paged_runner import paged_decode_step_jit

SCOPES = ("attn_proj", "paged_attention", "paged_lse", "attn_out", "mlp", "logits")


@pytest.fixture(scope="module")
def op_names():
    cfg = get_config("stablelm_1_6b").smoke()
    params = LM(cfg, attn_impl="naive", remat=None).init(jax.random.key(0))
    B, nb, bs, maxb = 2, 8, 4, 4
    pool = jnp.zeros((cfg.n_layers, nb, bs, cfg.n_kv_heads, cfg.hd), jnp.float32)
    ids = jnp.zeros((B, 1), jnp.int32)
    text = paged_decode_step_jit.lower(
        params, cfg, ids, ids, pool, pool, jnp.zeros((B, maxb), jnp.int32),
        jnp.ones((B,), jnp.int32), use_kernel=False,
    ).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("scope", SCOPES)
def test_paged_step_part_is_named(op_names, scope):
    assert any(f"/{scope}/" in name for name in op_names)


def test_second_pass_over_past_keys_is_in_paged_lse(op_names):
    # the log-sum-exp pass scores every past key again under its own scope
    assert any(n.endswith("/paged_lse/bkgd,bskd->bkgs/dot_general") for n in op_names)
