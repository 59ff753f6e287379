"""Per-arch smoke tests (reduced configs) + decode/prefill consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RunShape
from repro.configs.registry import get_config, lm_archs
from repro.launch.inputs import make_batch
from repro.models.transformer import LM

TRAIN = RunShape("smoke_train", 32, 2, "train")


@pytest.mark.parametrize("arch", lm_archs())
def test_arch_smoke_train_step(arch):
    """One forward/train step on CPU: output shapes + no NaNs (assignment)."""
    cfg = get_config(arch).smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, TRAIN)
    loss, grads = jax.value_and_grad(model.train_loss)(params, batch)
    assert np.isfinite(float(loss)), arch
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2)) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0.0, arch


@pytest.mark.parametrize("arch", lm_archs())
def test_arch_smoke_decode_step(arch):
    cfg = get_config(arch).smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))
    cache = model.init_cache(2, 32, enc_len=32 if cfg.is_encdec else 0)
    db = {
        "tokens": jnp.zeros((2, 1), jnp.int32),
        "positions": jnp.zeros(
            (2, 1, 3) if cfg.rope == "mrope" else (2, 1), jnp.int32
        ),
    }
    logits, cache2 = model.decode_step(params, db, cache)
    assert logits.shape[0] == 2
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    # split caches count appends in len_rec; recurrent caches in len
    total = int(cache2["len"]) + int(cache2.get("len_rec", 0))
    assert total == 1


@pytest.mark.parametrize("arch", ["stablelm_1_6b", "chatglm3_6b", "rwkv6_7b", "zamba2_7b"])
def test_decode_matches_teacher_forcing(arch):
    """Token-by-token decode logits == full-sequence forward logits."""
    cfg = get_config(arch).smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(1))
    S = 9
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (1, S)), jnp.int32
    )
    pos = jnp.arange(S, dtype=jnp.int32)[None]

    # teacher-forced: logits at the last position
    batch = {"tokens": toks, "positions": pos}
    full = model.prefill_logits(params, batch)

    # incremental decode
    cache = model.init_cache(1, S + 1)
    logits = None
    for t in range(S):
        db = {"tokens": toks[:, t : t + 1], "positions": pos[:, t : t + 1]}
        logits, cache = model.decode_step(params, db, cache)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full), rtol=2e-4, atol=2e-4
    )


def test_attn_impls_agree_end_to_end():
    cfg = get_config("stablelm_1_6b").smoke()
    batch = make_batch(cfg, TRAIN)
    vals = []
    for impl in ("naive", "chunked"):
        model = LM(cfg, attn_impl=impl, remat=None)
        params = model.init(jax.random.key(0))
        vals.append(float(model.train_loss(params, batch)))
    assert abs(vals[0] - vals[1]) < 1e-4


def test_vlm_patch_embeds_change_output():
    cfg = get_config("qwen2_vl_72b").smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, TRAIN)
    l1 = float(model.train_loss(params, batch))
    batch2 = dict(batch)
    batch2["patch_embeds"] = batch["patch_embeds"] + 1.0
    l2 = float(model.train_loss(params, batch2))
    assert l1 != l2


def test_param_counts_close_to_analytic():
    from repro.models.params import count_params

    for arch in ["stablelm_1_6b", "mistral_nemo_12b"]:
        cfg = get_config(arch)
        model = LM(cfg)
        defs = model.param_defs()
        from repro.models.params import ParamDef
        total = 0
        for d in jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef)):
            n = 1
            for s in d.shape:
                n *= s
            total += n
        analytic = cfg.n_params()
        # within 15% (vocab padding, norm params, analytic approximations)
        assert abs(total - analytic) / analytic < 0.15, (arch, total, analytic)
