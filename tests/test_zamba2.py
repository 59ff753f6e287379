"""Zamba2's pieces on the CPU: the SSD chunks against the recurrence, the
layer-indexed paged kernel with its log-sum-exp, the pool's state slots,
the published configuration and its smoke cut, and what the engine and the
launcher take."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.kv_pool import KVPoolConfig, PagedKVPool, lane_dim
from repro.kernels.paged_attention import ops as pg_ops
from repro.models import mamba2 as M2
from repro.models.rope import apply_rope
from repro.models.transformer import LM

RNG = np.random.default_rng(16)


def _ssd_inputs(B, S, H, P, N, G):
    f = lambda *s: jnp.asarray(RNG.normal(size=s), jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f(B, S, H))
    return f(B, S, H, P), dt, -jnp.exp(f(H)), f(B, S, G, N), f(B, S, G, N), f(B, H, P, N)


@pytest.mark.parametrize("S, chunk", [(11, 4), (16, 16), (5, 128)])
def test_ssd_chunks_match_the_recurrence(S, chunk):
    x, dt, A, Bm, Cm, h0 = _ssd_inputs(2, S, 4, 3, 5, 2)
    y, h = M2.ssd_chunked(x, dt, A, Bm, Cm, h0, chunk=chunk)
    hs, ys = h0, []
    for t in range(S):
        yt, hs = M2.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], hs)
        ys.append(yt)
    np.testing.assert_allclose(y, jnp.stack(ys, axis=1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h, hs, rtol=1e-4, atol=1e-4)


def test_ssd_decays_are_not_clamped():
    # dt * A = -50 a step: the state forgets at once, which a clamp of the
    # log-decay (as the RWKV scan has) would not
    x, dt, A, Bm, Cm, h0 = _ssd_inputs(1, 6, 2, 3, 4, 1)
    dt = jnp.full_like(dt, 50.0)
    y, h = M2.ssd_chunked(x, dt, jnp.full_like(A, -1.0), Bm, Cm, h0, chunk=4)
    last = dt[:, -1, :, None, None] * x[:, -1, :, :, None] * M2._heads(Bm[:, -1], 2)[:, :, None, :]
    np.testing.assert_allclose(h, last, rtol=1e-5, atol=1e-6)


def test_layer_kernel_matches_reference_with_its_log_sum_exp():
    L, nb, bs, Hkv, group, D, B, maxb = 3, 16, 8, 4, 1, 128, 3, 5
    q = jnp.asarray(RNG.normal(size=(B, Hkv * group, D)), jnp.float32)
    kp = jnp.asarray(RNG.normal(size=(L, nb, bs, Hkv, D)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(L, nb, bs, Hkv, D)), jnp.float32)
    lens = np.array([1, 17, 0], np.int32)
    tbl = np.full((B, maxb), -1, np.int32)
    for b in range(B):
        need = -(-int(lens[b]) // bs)
        tbl[b, :need] = RNG.choice(nb, size=need, replace=False)
    got = pg_ops.paged_attention_layer(q, kp, vp, jnp.asarray(tbl), jnp.asarray(lens), 2,
                                       scale=0.1, use_kernel=True)
    ref = pg_ops.paged_attention_layer(q, kp, vp, jnp.asarray(tbl), jnp.asarray(lens), 2,
                                       scale=0.1, use_kernel=False)
    np.testing.assert_allclose(got[0], ref[0], atol=2e-5)
    live = lens > 0                                    # no token: NEG_INF against -inf
    np.testing.assert_allclose(got[1][live], ref[1][live], atol=2e-5)
    assert np.all(np.asarray(got[1][~live]) < -1e29) and np.all(np.isneginf(ref[1][~live]))
    one = pg_ops.paged_attention(q, kp[2], vp[2], jnp.asarray(tbl), jnp.asarray(lens),
                                 scale=0.1, use_kernel=False)
    np.testing.assert_allclose(got[0][live], one[live], atol=2e-5)


def _state_pool(max_seqs=4, layers=3):
    return PagedKVPool(KVPoolConfig(num_blocks=8, block_size=4, kv_heads=1, head_dim=128,
                                    n_layers=1, max_seqs=max_seqs, max_blocks_per_seq=2,
                                    blocks_per_arena=4, state_layers=layers, conv_width=256,
                                    ssd_heads=2, ssd_width=128))


def test_state_slots_are_written_by_slot_and_nothing_else():
    pool = _state_pool()
    assert pool.conv.shape == (4, 3, 256) and pool.ssd.shape == (4, 3, 2, 128)
    # a prefill's state: (layers, 1, K-1, conv_dim) and (layers, 1, H, P, N)
    conv = jnp.arange(3 * 256, dtype=jnp.float32).reshape(3, 1, 2, 128)
    ssd = jnp.arange(3 * 256, dtype=jnp.float32).reshape(3, 1, 2, 8, 16) + 0.5
    pool.write_prompt_states(2, conv, ssd)
    np.testing.assert_array_equal(pool.conv[2], conv.reshape(3, 256))
    np.testing.assert_array_equal(pool.ssd[2], ssd.reshape(3, 2, 128))
    # a step's states: (layers, B, ...), rows to slots 3 and 0
    pool.write_token_states([3, 0], jnp.ones((3, 2, 256)) * jnp.array([1.0, 2.0])[:, None],
                            jnp.ones((3, 2, 2, 128)) * 7)
    np.testing.assert_array_equal(pool.conv[3], 1.0)
    np.testing.assert_array_equal(pool.conv[0], 2.0)
    np.testing.assert_array_equal(pool.conv[2], conv.reshape(3, 256))      # untouched
    np.testing.assert_array_equal(pool.conv[1], 0.0)
    np.testing.assert_array_equal(pool.ssd[1], 0.0)


def test_fork_copies_the_state_slot():
    pool = _state_pool()
    slot = pool.admit(4)
    pool.write_token_states([slot], jnp.full((3, 1, 256), 3.0), jnp.full((3, 1, 2, 128), 4.0))
    child = pool.fork(slot)
    np.testing.assert_array_equal(pool.conv[child], 3.0)
    np.testing.assert_array_equal(pool.ssd[child], 4.0)


def test_a_pool_without_state_layers_holds_no_state():
    pool = PagedKVPool(KVPoolConfig(num_blocks=8, blocks_per_arena=4))
    assert pool.conv is None and pool.ssd is None


def test_published_config_and_its_smoke_cut():
    cfg = get_config("zamba2_7b")
    assert cfg.hybrid_uses == (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)
    assert (cfg.hd, cfg.num_mem_blocks, cfg.adapter_rank, cfg.ssm_ngroups) == (224, 2, 128, 2)
    assert cfg.kv_layers == 13 and cfg.tie_embeddings and cfg.ssm_conv_bias
    # 81 Mamba2 layers, 2 blocks, 13 uses, the tied embedding, the final norm
    assert cfg.n_params() == (81 * 78_437_456 + 2 * 333_982_208 + 13 * 16_973_824
                              + 32000 * 3584 + 3584) == 7_356_749_648
    cut = dataclasses.replace(cfg, n_layers=18)
    assert cut.hybrid_uses == (6, 11, 17) and cut.kv_layers == 3
    smoke = cfg.smoke()
    assert smoke.hybrid_uses == (1, 2, 4) and smoke.num_mem_blocks == 2 and smoke.adapter_rank
    params = jax.eval_shape(LM(smoke).init, jax.random.key(0))
    assert params["shared"]["wq"].shape[0] == 2 and params["uses"]["lora_a"].shape[0] == 3


def test_rotate_half_rotary():
    cfg = dataclasses.replace(get_config("zamba2_7b").smoke(), rope_theta=100.0)
    x = jnp.asarray(RNG.normal(size=(1, 3, 2, 8)), jnp.float32)
    pos = jnp.arange(3)[None]
    got = apply_rope(cfg, x, pos)
    ang = np.arange(3)[:, None] * 100.0 ** (-np.arange(0, 8, 2) / 8)
    c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = np.asarray(x[..., :4]), np.asarray(x[..., 4:])
    np.testing.assert_allclose(got, np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["stablelm_1_6b", "zamba2_7b"])
def test_engine_takes_hybrids_and_refuses_recurrent_and_encdec(arch):
    from repro.launch.serve import pool_config
    from repro.serve.engine import ServeEngine

    for other in ("rwkv6_7b", "seamless_m4t_medium"):
        cfg = get_config(other).smoke()
        with pytest.raises(ValueError, match="paged engine"):
            ServeEngine(LM(cfg), None, pool_config(cfg, max_seqs=2))
    cfg = get_config(arch).smoke()
    pc = pool_config(cfg, max_seqs=2)
    model = LM(cfg, attn_impl="naive", remat=None)
    eng = ServeEngine(model, model.init(jax.random.key(0)), pc)
    if cfg.family == "hybrid":
        assert (pc.n_layers, pc.head_dim, pc.state_layers) == (3, lane_dim(cfg.hd), 5)
        assert (pc.conv_width, pc.ssd_heads, pc.ssd_width) == (
            3 * (256 + 2 * 2 * 16), 16, 16 * 16)
        assert eng.pool.ssd.shape == (2, 5, 16, 256)
    else:
        assert (pc.kv_heads, pc.head_dim, pc.n_layers) == (cfg.n_kv_heads, cfg.hd, cfg.n_layers)
        assert (pc.state_layers, pc.conv_width, pc.ssd_heads, pc.ssd_width) == (0, 0, 0, 0)
        assert eng.pool.ssd is None
    for field in ("kv_heads", "head_dim", "n_layers", "state_layers", "ssd_width"):
        bad = dataclasses.replace(pc, **{field: getattr(pc, field) + 2})
        with pytest.raises(ValueError, match=f"does not fit.*'{field}'"):
            ServeEngine(model, None, bad)
