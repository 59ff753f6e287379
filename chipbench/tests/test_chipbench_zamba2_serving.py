"""The hybrid serving path at tiny widths in float32 on the CPU, against the
zamba2 family's plain reference: ``ServeEngine`` -> prefill (the model's
chunked ``decode_step``) -> the pool's pages and state slots ->
``paged_decode_step``, its logits compared position by position."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tinyzamba
from chipbench import reference, weights
from chipbench.families import zamba2

#: Logits agree to float32 summation order: the chunked SSD against the
#: per-token recurrence, the paged softmax merge against one softmax, and
#: the compiler's dot order.  Measured at most 5.1e-4 (logits of std ~8);
#: 2e-3 leaves 4x room for other CPUs, and each planted fault below moves
#: the logits by more than 100x that
ATOL = 2e-3
PROMPTS = [[5] * 7, list(range(20)), list(range(40, 53))]
MAX_NEW = 12


@pytest.fixture(scope="module")
def tiny():
    cfg = json.loads((tinyzamba.ROOT / "chipbench" / "configs" /
                      "zamba2_7b_18layers.json").read_text())
    cfg.update(tinyzamba.PUBLISHED, overrides=dict(
        tinyzamba.PROGRAM, dtype="float32", kv_cache_dtype="float32"))
    cfg["reference"].update(compute_dtype="float32", kv_dtype="float32")
    spec = reference.spec_of(cfg)
    from repro.models.transformer import LM

    pcfg = zamba2.program_config(cfg, spec)
    model = LM(pcfg, attn_impl="naive", remat=None)
    padded = jax.eval_shape(model.init, jax.random.key(0))["embed"]["tok"].shape[0]
    w = weights.make_weights(zamba2.weight_shapes(spec, padded), zamba2.FAN_IN, spec["vocab"], 7)
    mix = {"sessions": 3, "block_size": 8, "blocks_per_arena": 4,
           "prompt_tokens": {"values": [40]}, "answer_tokens": {"values": [MAX_NEW]}}
    return {"spec": spec, "w": w, "model": model, "pool": zamba2.pool_config(pcfg, mix)}


def serve(tiny, prompts, *, params=None, submit_at=None, preempt=None):
    """Serve ``prompts`` greedily; returns {rid: (tokens, logits rows)}, the
    rows being each of the request's tokens' logits.  ``submit_at`` maps a
    rid to the step before which it is submitted (default 0); ``preempt``
    = (rid, step): that request is preempted after that step."""
    from repro.serve.engine import Request, ServeEngine

    model = tiny["model"]
    eng = ServeEngine(model, params or zamba2.program_params(tiny["w"], model), tiny["pool"])
    rows = {}
    prefill, step = eng._decode_step, eng._paged_step

    def on_prefill(params, batch, cache):
        logits, cache = prefill(params, batch, cache)
        ctx = np.asarray(batch["tokens"][0]).tolist()
        req = next(r for r in eng.live.values() if r.prompt + r.out[:-1] == ctx)
        if not req.out:                 # a resume's recompute samples nothing
            rows.setdefault(req.rid, []).append(np.asarray(logits[0]))
        return logits, cache

    def on_step(*a, **k):
        out = step(*a, **k)
        for i, slot in enumerate(sorted(eng.live)):
            rows.setdefault(eng.live[slot].rid, []).append(np.asarray(out[0][i]))
        return out

    eng._decode_step, eng._paged_step = on_prefill, on_step
    reqs = [Request(rid=i, prompt=list(p), max_new=MAX_NEW) for i, p in enumerate(prompts)]
    submit_at = submit_at or {}
    for n in range(200):
        for r in reqs:
            if submit_at.get(r.rid, 0) == n:
                eng.submit(r)
        if preempt and n == preempt[1]:
            eng._preempt(next(s for s, r in eng.live.items() if r.rid == preempt[0]))
        if not eng.step() and n >= max(submit_at.values(), default=0):
            break
    assert all(r.status == "done" for r in reqs)
    return {r.rid: (r.out, np.stack(rows[r.rid])) for r in reqs}


def reference_rows(tiny, prompt, out):
    """The reference's logits for each served token of one request."""
    return np.asarray(zamba2.forward_logits(tiny["w"], tiny["spec"], prompt + out[:-1],
                                            len(prompt) - 1, len(out), 64))


def test_prefill_then_decode_match_the_reference(tiny):
    got = serve(tiny, PROMPTS)
    for rid, prompt in enumerate(PROMPTS):
        out, rows = got[rid]
        assert rows.shape[0] == MAX_NEW            # the prefill's row, then each step's
        ref = reference_rows(tiny, prompt, out)
        np.testing.assert_allclose(rows[:, :tiny["spec"]["vocab"]], ref, atol=ATOL, rtol=0)
        assert out == np.argmax(ref, axis=-1).tolist()


def test_dense_cache_decode_step_matches_the_reference(tiny):
    model, prompt = tiny["model"], PROMPTS[1]
    params = zamba2.program_params(tiny["w"], model)
    S = len(prompt)
    cache = model.init_cache(1, S + 4, recent_size=S + 4)
    toks = jnp.asarray([prompt], jnp.int32)
    logits, cache = model.decode_step(params, {"tokens": toks[:, :-4], "positions":
                                               jnp.arange(S - 4)[None]}, cache)
    rows = [logits[0]]
    for t in range(S - 4, S):                       # the last four one token at a time
        logits, cache = model.decode_step(params, {"tokens": toks[:, t:t + 1],
                                                   "positions": jnp.full((1, 1), t)}, cache)
        rows.append(logits[0])
    ref = np.asarray(zamba2.forward_logits(tiny["w"], tiny["spec"], prompt, S - 5, 5, 32))
    np.testing.assert_allclose(np.stack(rows)[:, :tiny["spec"]["vocab"]], ref, atol=ATOL, rtol=0)


def test_a_sequence_admitted_mid_batch_leaves_its_neighbours_alone(tiny):
    alone = serve(tiny, PROMPTS[:2])
    joined = serve(tiny, PROMPTS, submit_at={2: 4})
    for rid in (0, 1):
        assert joined[rid][0] == alone[rid][0]
        np.testing.assert_allclose(joined[rid][1], alone[rid][1], atol=1e-5, rtol=0)
    out, rows = joined[2]
    np.testing.assert_allclose(rows[:, :tiny["spec"]["vocab"]],
                               reference_rows(tiny, PROMPTS[2], out), atol=ATOL, rtol=0)


def test_a_preempted_sequence_resumes_exactly(tiny):
    plain = serve(tiny, PROMPTS)
    resumed = serve(tiny, PROMPTS, preempt=(1, 5))
    for rid in range(3):
        assert resumed[rid][0] == plain[rid][0]
    # the resumed request's rows: its steps before, then its steps after
    # (the recompute samples nothing); its state is rebuilt by the chunked
    # form, so they agree to summation order
    np.testing.assert_allclose(resumed[1][1], plain[1][1], atol=ATOL, rtol=0)


def _faulty_params(tiny, fault):
    params = zamba2.program_params(tiny["w"], tiny["model"])
    if fault == "blocks_swapped":
        params["shared"] = jax.tree.map(lambda a: a[::-1], params["shared"])
    elif fault == "adapter_of_another_use":
        params["uses"] = dict(params["uses"], **{
            k: jnp.roll(params["uses"][k], 1, axis=0) for k in ("lora_a", "lora_b")})
    return params


@pytest.mark.parametrize("fault", ["state_write_dropped", "blocks_swapped", "concat_left_out",
                                   "adapter_of_another_use"])
def test_planted_fault_breaks_agreement(tiny, monkeypatch, fault):
    import repro.models.transformer as TF
    from repro.core.kv_pool import PagedKVPool

    if fault == "state_write_dropped":
        monkeypatch.setattr(PagedKVPool, "write_token_states", lambda *a, **k: None)
    if fault == "concat_left_out":
        keep = TF.shared_block_input
        monkeypatch.setattr(TF, "shared_block_input",
                            lambda sp, cfg, x, e: keep(sp, cfg, x, jnp.zeros_like(e)))
    jax.clear_caches()
    try:
        got = serve(tiny, PROMPTS, params=_faulty_params(tiny, fault))
    finally:
        jax.clear_caches()
    worst = max(np.abs(rows[:, :tiny["spec"]["vocab"]]
                       - reference_rows(tiny, PROMPTS[rid], out)).max()
                for rid, (out, rows) in got.items())
    assert worst > 100 * ATOL
