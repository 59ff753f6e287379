"""Serving engine: paged decode parity with dense decode, continuous
batching under pool pressure, fork (RowClone) path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.kv_pool import KVPoolConfig
from repro.serve.engine import Request, ServeEngine
from repro.models.transformer import LM


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("stablelm_1_6b").smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))
    return model, params


def _pool_cfg(cfg, **kw):
    base = dict(
        num_blocks=128, block_size=8, kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        n_layers=cfg.n_layers, max_seqs=8, max_blocks_per_seq=16,
        blocks_per_arena=16, policy="puma", dtype="float32",
    )
    base.update(kw)
    return KVPoolConfig(**base)


def _dense_generate(model, params, prompt, max_new):
    toks = jnp.asarray([prompt], jnp.int32)
    S = len(prompt)
    cache = model.init_cache(1, S + max_new + 1)
    batch = {"tokens": toks, "positions": jnp.arange(S, dtype=jnp.int32)[None]}
    logits, cache = model.decode_step(params, batch, cache)
    out = [int(jnp.argmax(logits[0]))]
    for t in range(max_new - 1):
        batch = {
            "tokens": jnp.asarray([[out[-1]]], jnp.int32),
            "positions": jnp.asarray([[S + t]], jnp.int32),
        }
        logits, cache = model.decode_step(params, batch, cache)
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_paged_engine_matches_dense_decode(model_and_params):
    model, params = model_and_params
    cfg = model.cfg
    eng = ServeEngine(model, params, _pool_cfg(cfg))
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(0, cfg.vocab_size, size=int(rng.integers(4, 18))))
        for _ in range(4)
    ]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=6))
    done = eng.run()
    assert len(done) == 4
    for req in done:
        ref = _dense_generate(model, params, prompts[req.rid], 6)
        assert req.out == ref, (req.rid, req.out, ref)


def test_engine_takes_kernel_path_only_on_tpu(model_and_params, monkeypatch):
    model, params = model_and_params
    assert jax.default_backend() == "cpu"
    assert ServeEngine(model, params, _pool_cfg(model.cfg)).use_kernel is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ServeEngine(model, params, _pool_cfg(model.cfg)).use_kernel is True


def test_continuous_batching_under_pressure(model_and_params):
    model, params = model_and_params
    cfg = model.cfg
    # tiny pool: forces queueing + admission as slots free up
    eng = ServeEngine(model, params, _pool_cfg(cfg, num_blocks=32, max_seqs=2))
    rng = np.random.default_rng(1)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=list(rng.integers(0, 64, 6)), max_new=4))
    done = eng.run()
    assert len(done) == 5                      # everyone eventually served
    m = eng.metrics()
    assert m["tokens"] >= 5 * 3
    assert eng.pool.pool.free_tiles() == eng.pool.pool.total_tiles


def test_fork_shares_prefix(model_and_params):
    model, params = model_and_params
    cfg = model.cfg
    eng = ServeEngine(model, params, _pool_cfg(cfg))
    eng.submit(Request(rid=0, prompt=[1, 2, 3, 4, 5, 6, 7, 8, 9], max_new=4))
    # admit + prefill via one engine step
    eng.step()
    parent_slot = next(iter(eng.live))
    forked = eng.pool.fork(parent_slot)
    assert forked is not None
    # forked sequence sees identical KV content (RowClone block copy)
    tbl = eng.pool.block_table()
    pb = tbl[parent_slot][tbl[parent_slot] >= 0]
    fb = tbl[forked][tbl[forked] >= 0]
    assert len(pb) == len(fb) and list(pb) != list(fb)
    k = np.asarray(eng.pool.k)
    v = np.asarray(eng.pool.v)
    np.testing.assert_array_equal(k[:, pb], k[:, fb])
    np.testing.assert_array_equal(v[:, pb], v[:, fb])
    # both generate the same continuation from here
    eng.live[forked] = Request(rid=1, prompt=[], max_new=4,
                               out=list(eng.live[parent_slot].out))
    done = eng.run()
    outs = {r.rid: r.out for r in done}
    assert outs[0][-3:] == outs[1][-3:]


def _pressured_run(model, params):
    """Six requests through a pool of 8 pages of 8 tokens with four slots,
    in bf16: pages fill up, so the engine preempts and resumes."""
    cfg = model.cfg
    eng = ServeEngine(model, params, _pool_cfg(
        cfg, num_blocks=8, blocks_per_arena=4, max_seqs=4, dtype="bfloat16"))
    rng = np.random.default_rng(3)
    for i in range(6):
        n = int(rng.integers(3, 21))
        eng.submit(Request(rid=i, prompt=[int(t) for t in rng.integers(0, cfg.vocab_size, n)],
                           max_new=10))
    return eng, {r.rid: r.out for r in eng.run()}


#: served by the engine as it was when each layer of each sequence was
#: written by its own eager call, on the CPU
RECORDED = {
    0: [729, 281, 750, 151, 196, 1735, 25, 1585, 11, 1209],
    1: [946, 108, 237, 1616, 908, 725, 1801, 411, 1645, 1096],
    2: [143, 750, 883, 914, 387, 1566, 481, 2037, 208, 1261],
    3: [1280, 698, 1656, 702, 650, 1631, 302, 1853, 80, 1322],
    4: [1149, 1815, 815, 321, 2035, 883, 355, 557, 770, 949],
    5: [874, 55, 782, 1220, 770, 1661, 1772, 511, 1553, 1928],
}


def test_served_tokens_match_the_per_layer_writes(model_and_params):
    eng, outs = _pressured_run(*model_and_params)
    assert eng.preemptions == 3
    assert outs == RECORDED


def test_one_pool_write_per_decode_step_and_per_prefill(model_and_params, monkeypatch):
    from repro.core.kv_pool import PagedKVPool

    calls = {"write_token_kv": 0, "write_prompt_kv": 0}
    for name in calls:
        def counted(self, *a, _f=getattr(PagedKVPool, name), _n=name):
            calls[_n] += 1
            return _f(self, *a)
        monkeypatch.setattr(PagedKVPool, name, counted)
    eng, outs = _pressured_run(*model_and_params)
    assert len(outs) == 6 and eng.preemptions > 0
    assert calls["write_token_kv"] == eng.steps
    # every admission prefills once; a preempted request prefills again
    assert calls["write_prompt_kv"] == 6 + eng.preemptions
