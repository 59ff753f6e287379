"""Hardened serving path (ISSUE 7): loud rejection of never-admissible
requests, bounded-lookahead admission (head-of-line fix), deadlines, and
LRU preemption with bit-exact recompute-on-resume."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.kv_pool import KVPoolConfig
from repro.models.transformer import LM
from repro.robustness import (
    DeadlineExceeded,
    FaultInjector,
    FaultPlan,
    RequestRejected,
    check_engine,
)
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("stablelm_1_6b").smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))
    return model, params


def _pool_cfg(cfg, **kw):
    base = dict(
        num_blocks=16, block_size=8, kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        n_layers=cfg.n_layers, max_seqs=2, max_blocks_per_seq=16,
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


def test_never_admissible_request_rejected_at_submit(model_and_params):
    model, params = model_and_params
    eng = ServeEngine(model, params, _pool_cfg(model.cfg))
    # capacity: min(16, 16) blocks * 8 tokens = 128 tokens; ask for more
    with pytest.raises(RequestRejected) as ei:
        eng.submit(Request(rid=0, prompt=list(range(120)), max_new=20))
    assert ei.value.ctx["blocks_needed"] > eng.pool.capacity_blocks
    with pytest.raises(RequestRejected):
        eng.submit(Request(rid=1, prompt=[], max_new=4))
    # loudly recorded, not silently dropped
    assert [r.rid for r in eng.rejected] == [0, 1]
    assert all(r.error is not None for r in eng.rejected)
    assert not eng.queue
    check_engine(eng).assert_ok()


def test_stalled_queue_is_rejected_with_report(model_and_params):
    model, params = model_and_params
    inj = FaultInjector(FaultPlan(alloc_miss_rate=1.0))   # admission never works
    eng = ServeEngine(model, params, _pool_cfg(model.cfg),
                      injector=inj, stall_patience=2)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new=2))
    with pytest.raises(RequestRejected) as ei:
        eng.run(max_steps=20)
    report = ei.value.ctx["report"]
    assert report["free_tiles"] == report["total_tiles"]  # pool idle yet stuck
    assert eng.rejected[0].status == "rejected"
    assert not eng.queue and not eng.live                 # zero silent drops
    check_engine(eng).assert_ok()
    # the loud path is also visible without raising
    done = ServeEngine(model, params, _pool_cfg(model.cfg),
                       injector=FaultInjector(FaultPlan(alloc_miss_rate=1.0)),
                       stall_patience=2)
    done.submit(Request(rid=0, prompt=[1, 2, 3], max_new=2))
    assert done.run(max_steps=20, raise_on_error=False) == []
    assert len(done.rejected) == 1


def test_lookahead_admission_fixes_head_of_line_blocking(model_and_params):
    model, params = model_and_params
    eng = ServeEngine(model, params, _pool_cfg(model.cfg, max_seqs=2))
    rng = np.random.default_rng(2)
    big_prompt = list(rng.integers(0, 64, 90))     # 12 blocks: blocked early
    small_prompt = list(rng.integers(0, 64, 8))    # 1 block: always fits
    eng.submit(Request(rid=0, prompt=list(rng.integers(0, 64, 40)), max_new=4))
    eng.submit(Request(rid=1, prompt=big_prompt, max_new=2))
    eng.submit(Request(rid=2, prompt=small_prompt, max_new=4))
    done = eng.run()
    assert sorted(r.rid for r in done) == [0, 1, 2]    # nobody starves
    by_rid = {r.rid: r for r in done}
    # the small request jumped the blocked big one (bounded lookahead)
    assert by_rid[2].admit_clock < by_rid[1].admit_clock
    check_engine(eng).assert_ok()
    assert eng.pool.pool.free_tiles() == eng.pool.pool.total_tiles


def test_deadline_cancels_queued_request(model_and_params):
    model, params = model_and_params
    eng = ServeEngine(model, params, _pool_cfg(model.cfg, max_seqs=1))
    eng.submit(Request(rid=0, prompt=[1, 2, 3, 4], max_new=8))
    eng.submit(Request(rid=1, prompt=[5, 6, 7, 8], max_new=4,
                       deadline_steps=2))       # expires while queued
    done = eng.run()                            # cancellation does not raise
    assert [r.rid for r in done] == [0]
    assert len(eng.cancelled) == 1
    victim = eng.cancelled[0]
    assert victim.rid == 1 and victim.status == "cancelled"
    assert isinstance(victim.error, DeadlineExceeded)
    check_engine(eng).assert_ok()


def test_preemption_resumes_with_bit_exact_recompute(model_and_params):
    model, params = model_and_params
    cfg = model.cfg
    # 8 blocks of 4 tokens: two growing sequences must collide mid-decode
    eng = ServeEngine(
        model, params,
        _pool_cfg(cfg, num_blocks=8, block_size=4, blocks_per_arena=8,
                  max_seqs=2, max_blocks_per_seq=8),
    )
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, 64, 10)) for _ in range(2)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=10))
    done = eng.run()
    assert sorted(r.rid for r in done) == [0, 1]
    assert eng.preemptions >= 1                 # the collision happened
    assert max(r.preemptions for r in done) >= 1
    for req in done:
        ref = _dense_generate(model, params, prompts[req.rid], 10)
        assert req.out == ref, (req.rid, req.preemptions)
    check_engine(eng).assert_ok()
    assert eng.pool.pool.free_tiles() == eng.pool.pool.total_tiles


def hyp_seeds(func):
    """Drive ``func(..., seed=...)`` with hypothesis when installed; fall
    back to fixed seeds otherwise (same contract as the churn suite)."""
    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        return pytest.mark.parametrize("seed", [0xC0FFEE, 0xBADF00D])(func)
    return settings(max_examples=2, deadline=None)(
        given(seed=st.integers(0, 2**32 - 1))(func)
    )


@hyp_seeds
def test_contended_run_matches_uncontended_bit_exactly(model_and_params, seed):
    """Property (ISSUE 9 satellite): whatever preemption/recompute churn a
    starved pool inflicts, every request decodes the exact tokens it would
    have produced alone on a roomy pool — placement is invisible to the
    math."""
    model, params = model_and_params
    cfg = model.cfg
    rng = np.random.default_rng(seed)
    reqs = [
        (int(rng.integers(8, 13)), list(rng.integers(0, 64, int(n))))
        for n in rng.integers(8, 13, size=3)
        for _ in [0]
    ]
    reqs = [(len(p), p) for _, p in reqs]

    def run(pool_kw):
        eng = ServeEngine(model, params, _pool_cfg(cfg, **pool_kw))
        for i, (_, p) in enumerate(reqs):
            eng.submit(Request(rid=i, prompt=list(p), max_new=10))
        done = eng.run()
        check_engine(eng).assert_ok()
        assert eng.pool.pool.free_tiles() == eng.pool.pool.total_tiles
        return eng, {r.rid: list(r.out) for r in done}

    # starved: 8 blocks x 4 tokens; any two live seqs (>=18 tokens each by
    # construction) overflow the pool mid-decode, forcing preempt+recompute
    contended, out_c = run(dict(num_blocks=8, block_size=4, max_seqs=2,
                                blocks_per_arena=8, max_blocks_per_seq=8))
    # roomy: 4x the blocks, every sequence fits untouched
    uncontended, out_u = run(dict(num_blocks=32, block_size=4, max_seqs=4,
                                  blocks_per_arena=8, max_blocks_per_seq=8))
    assert contended.preemptions >= 1
    assert uncontended.preemptions == 0
    assert set(out_c) == set(out_u) == {0, 1, 2}
    assert out_c == out_u


def test_step_hooks_get_isolated_snapshots(model_and_params):
    """Regression (ISSUE 10 satellite): each step hook gets its own copy of
    the step sample, and hooks registered/removed from inside a hook do not
    perturb the current iteration — a maintenance consumer that mutates its
    sample (as the watermark bookkeeping does) must not leak an
    inconsistent read into a sampler running in the same tick."""
    model, params = model_and_params
    eng = ServeEngine(model, params, _pool_cfg(model.cfg))

    seen_by_b = []

    def hook_a(e, sample):
        # hostile consumer: clobbers every field, then empties its dict,
        # and deregisters itself mid-iteration
        for k in list(sample):
            sample[k] = -1.0
        sample.clear()
        if hook_a in e.step_hooks:
            e.step_hooks.remove(hook_a)

    def hook_b(e, sample):
        seen_by_b.append(dict(sample))

    eng.step_hooks.append(hook_a)
    eng.step_hooks.append(hook_b)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new=4))
    eng.run()
    check_engine(eng).assert_ok()

    assert seen_by_b, "second hook never ran"
    for sample in seen_by_b:
        # pristine values despite hook_a's clobbering in the same tick
        assert sample, "hook saw an emptied sample"
        assert all(v >= 0 for v in sample.values()), sample
        assert 0.0 <= sample["used_fraction"] <= 1.0
    # hook_a removed itself after the first step without skipping hook_b
    assert hook_a not in eng.step_hooks
    assert len(seen_by_b) == eng.clock
