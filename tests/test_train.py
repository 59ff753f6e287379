"""Training loop: convergence, checkpoint/restart, failure recovery,
gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig, synth_batch
from repro.models.transformer import LM
from repro.optim import adamw as opt_mod
from repro.optim import compression as comp
from repro.train.step import build_train_step
from repro.train.trainer import Trainer, TrainerConfig


def _mk_model():
    cfg = get_config("stablelm_1_6b").smoke()
    return LM(cfg, attn_impl="naive", remat=None), cfg


def _data_cfg(cfg, seq=32, batch=4):
    return DataConfig(vocab_size=cfg.vocab_size, seq_len=seq, batch_per_shard=batch)


def test_loss_decreases(tmp_path):
    model, cfg = _mk_model()
    tcfg = TrainerConfig(
        total_steps=40, ckpt_every=100, ckpt_dir=str(tmp_path), log_every=1000
    )
    ocfg = opt_mod.AdamWConfig(lr=5e-3, warmup_steps=5, total_steps=40)
    out = Trainer(
        model, _data_cfg(cfg, seq=64, batch=8), ocfg, tcfg, log=lambda s: None
    ).run()
    hist = [m["loss"] for _, m in out["history"]]
    assert np.mean(hist[-5:]) < np.mean(hist[:5]) - 0.5, hist


def test_checkpoint_resume_bit_exact(tmp_path):
    model, cfg = _mk_model()
    ocfg = opt_mod.AdamWConfig(warmup_steps=2, total_steps=20)

    # run 1: straight through 10 steps
    t1 = TrainerConfig(total_steps=10, ckpt_every=100, ckpt_dir=str(tmp_path / "a"),
                       log_every=1000)
    outA = Trainer(model, _data_cfg(cfg), ocfg, t1, log=lambda s: None).run()

    # run 2: 5 steps (ckpt at 5), then a fresh Trainer resumes to 10
    t2 = TrainerConfig(total_steps=5, ckpt_every=5, ckpt_dir=str(tmp_path / "b"),
                       log_every=1000)
    Trainer(model, _data_cfg(cfg), ocfg, t2, log=lambda s: None).run()
    t3 = TrainerConfig(total_steps=10, ckpt_every=100, ckpt_dir=str(tmp_path / "b"),
                       log_every=1000)
    outB = Trainer(model, _data_cfg(cfg), ocfg, t3, log=lambda s: None).run()

    for a, b in zip(jax.tree.leaves(outA["params"]), jax.tree.leaves(outB["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_failure_recovery(tmp_path):
    model, cfg = _mk_model()
    ocfg = opt_mod.AdamWConfig(warmup_steps=2, total_steps=20)
    boom = {"armed": True}

    def failure_hook(step):
        if step == 7 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected node failure")

    tcfg = TrainerConfig(total_steps=10, ckpt_every=5, ckpt_dir=str(tmp_path),
                         log_every=1000)
    out = Trainer(model, _data_cfg(cfg), ocfg, tcfg,
                  failure_hook=failure_hook, log=lambda s: None).run()
    assert out["recoveries"] == 1
    # reached the target despite the failure
    steps = [s for s, _ in out["history"]]
    assert max(steps) == 9


def test_grad_accumulation_matches_full_batch():
    model, cfg = _mk_model()
    ocfg = opt_mod.AdamWConfig(warmup_steps=0, total_steps=10)
    params = model.init(jax.random.key(0))
    opt1 = opt_mod.init_opt_state(params)
    batch = {
        k: jnp.asarray(v) for k, v in synth_batch(_data_cfg(cfg), 0, 0).items()
    }
    s1 = build_train_step(model, ocfg, accum_steps=1)
    s2 = build_train_step(model, ocfg, accum_steps=2)
    p1, _, m1 = jax.jit(s1)(params, opt1, batch)
    p2, _, m2 = jax.jit(s2)(params, opt_mod.init_opt_state(params), batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_compression_error_feedback_unbiased():
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)}
    err = comp.init_error_state(g)
    acc = np.zeros((64, 64), np.float64)
    acc_raw = np.zeros((64, 64), np.float64)
    for step in range(50):
        gs = {"w": g["w"] * (1.0 + 0.01 * step)}
        deq, err = comp.compress_grads(gs, err)
        acc += np.asarray(deq["w"], np.float64)
        acc_raw += np.asarray(gs["w"], np.float64)
    # error feedback keeps the accumulated quantized stream close to the
    # accumulated true stream (bounded by one quantization step)
    scale = np.abs(acc_raw).max()
    assert np.abs(acc - acc_raw).max() / scale < 0.01


def test_compressed_psum_on_one_device_mesh():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax import shard_map
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 128)), jnp.float32)
    f = shard_map(
        lambda v: comp.compressed_psum(v, "data"),
        mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False,
    )
    out = f(x)
    assert float(jnp.max(jnp.abs(out - x))) < np.abs(x).max() / 100
