"""Compile the main-path kernels and the paged decode step for a described
TPU v5e — no chip needed, the TPU compiler refuses illegal tilings and
programs that do not fit the way the chip would.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and test workers all import this file.
"""
import dataclasses
import functools
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core import kv_pool
from repro.kernels.paged_attention import kernel as paged_k
from repro.kernels.pud_bulk import kernel as pud_k
from repro.launch.serve import pool_config
from repro.models.transformer import LM
from repro.serve.engine import COMPUTE_DTYPE_LEAVES
from repro.serve.paged_runner import paged_decode_step

V5E_HBM_BYTES = 16 * 10**9        # one v5e chip, published: 16 GB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the kernel, not a fallback
    return compiled


# (Hq, Hkv, D): stablelm-1.6b, chatglm3-6b, granite-moe-1b, granite-34b, zamba2-7b
@pytest.mark.parametrize("hq,hkv,d", [(32, 32, 64), (32, 2, 128), (16, 8, 64), (48, 1, 128),
                                      (32, 32, 224)])
def test_paged_attention_compiles(spec, hq, hkv, d):
    B, nb, bs, maxb = 8, 512, 16, 32
    pool = spec((nb, bs, hkv, d), jnp.bfloat16)
    _compile(
        functools.partial(paged_k.paged_attention, scale=d ** -0.5, interpret=False),
        spec((B, hkv, hq // hkv, d), jnp.bfloat16), pool, pool,
        spec((B, maxb), jnp.int32), spec((B,), jnp.int32),
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_block_copy_compiles_at_stablelm_page(spec, dtype):
    # every layer's pages of a 512-page stablelm-1.6b pool: (L*nb, bs, KV, hd)
    _compile(
        functools.partial(pud_k.block_copy, interpret=False),
        spec((24 * 512, 16, 32, 64), dtype), spec((24 * 9, 2), jnp.int32),
    )


def test_bulk_op_compiles(spec):
    x = spec((1024, pud_k.LANES), jnp.int32)
    _compile(functools.partial(pud_k.bulk_op, op="maj", interpret=False), x, x, x)


def _stablelm_step(spec, monkeypatch, matrices=None):
    """stablelm-1.6b's paged step at its published widths, depth cut to 2
    layers, compiled for one v5e, and its parameters' shapes; ``matrices``
    is the dtype of the weight matrices (default: f32, as ``LM.init`` makes
    them)."""
    cfg = dataclasses.replace(get_config("stablelm_1_6b"), n_layers=2)
    pc = pool_config(cfg, max_seqs=8)
    shapes = jax.eval_shape(LM(cfg, attn_impl="naive", remat=None).init, jax.random.key(0))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: spec(a.shape, matrices if matrices and path[-1].key in
                             COMPUTE_DTYPE_LEAVES else a.dtype), shapes)
    pool = spec(
        (cfg.n_layers, pc.num_blocks, pc.block_size, pc.kv_heads, pc.head_dim),
        jnp.dtype(pc.dtype),
    )
    B = 8
    # the kernel picks interpret mode from the backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        compiled = _compile(
            lambda p, *xs: paged_decode_step(p, cfg, *xs, use_kernel=True),
            params, spec((B, 1), jnp.int32), spec((B, 1), jnp.int32), pool, pool,
            spec((B, pc.max_blocks_per_seq), jnp.int32), spec((B,), jnp.int32),
        )
    finally:
        jax.clear_caches()       # drop traces made while the backend was faked
    return compiled, shapes


def test_paged_decode_step_compiles_at_published_width(spec, monkeypatch):
    compiled, _ = _stablelm_step(spec, monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


def _weight_casts(hlo: str, sizes) -> list:
    """(operand, result) types of every ``convert`` in ``hlo`` whose operand
    is f32 with as many elements as one of ``sizes``."""
    types = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", hlo))
    casts = []
    for dst, src in re.findall(r"= (\w+\[[\d,]*\])\S* convert\(%([\w.\-]+)\)", hlo):
        t = types.get(src, "")
        if t.startswith("f32[") and math.prod(int(d) for d in t[4:-1].split(",") if d) in sizes:
            casts.append((t, dst))
    return casts


def test_paged_step_casts_no_weight_held_in_the_compute_dtype(spec, monkeypatch):
    # the same step with the matrices in bf16, as ServeEngine holds them where
    # its copy fits: no weight is cast in the step, and no temporary is larger
    f32, shapes = _stablelm_step(spec, monkeypatch)
    bf16, _ = _stablelm_step(spec, monkeypatch, jnp.bfloat16)
    sizes = set()                 # a matrix, and one layer of a stacked one
    for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        if path[-1].key in COMPUTE_DTYPE_LEAVES:
            sizes |= {math.prod(a.shape)} | ({math.prod(a.shape[1:])} if a.ndim > 2 else set())
    assert _weight_casts(f32.as_text(), sizes)
    assert not _weight_casts(bf16.as_text(), sizes)
    assert bf16.memory_analysis().temp_size_in_bytes <= f32.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("write", ["token", "prompt"])
def test_pool_writes_compile_in_place(spec, write):
    # stablelm-1.6b's pool at 1088 pages: its head dimension of 64 puts the
    # page axis in the lanes, where a scatter would turn the pools row-major
    shape = (24, 1088, 16, 32, 64)
    pool = spec(shape, jnp.bfloat16)
    if write == "token":
        B = 8
        kv = spec((24, B, 32, 64), jnp.bfloat16)
        lowered = kv_pool._write_tokens.lower(
            pool, pool, spec((B,), jnp.int32), spec((B,), jnp.int32), kv, kv)
    else:
        S = 2048
        kv = spec((24, 1, S, 32, 64), jnp.bfloat16)
        lowered = kv_pool._write_pages.lower(
            pool, pool, spec((S // 16, 3), jnp.int32), spec((), jnp.int32), kv, kv)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = 24 * 1088 * 16 * 32 * 64 * 2
    assert mem.alias_size_in_bytes >= 2 * pool_bytes       # both pools donated
    copies = [l for l in compiled.as_text().splitlines() if " copy(" in l]
    assert not [l for l in copies if "bf16[24,1088,16,32,64]" in l]
    if write == "token":
        assert mem.temp_size_in_bytes < pool_bytes // 100


def _zamba2_cell():
    """The zamba2 cell's program config and pool, from the benchmark's files."""
    import sys

    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from chipbench import reference, traffic
    from chipbench.families import zamba2

    cfg_file = json.loads((root / "chipbench/configs/zamba2_7b_18layers.json").read_text())
    pcfg = zamba2.program_config(cfg_file, reference.spec_of(cfg_file))
    mix = traffic.load_mix(root / "chipbench/traffic/longprompt_16s.json")
    return pcfg, zamba2.pool_config(pcfg, mix)


def test_hybrid_paged_step_compiles_at_the_cell_shapes(spec, monkeypatch, capsys):
    # zamba2-7b cut to 18 layers, batch 16, the cell's 2176-page pool and
    # state slots: the step fits one chip with the whole model and pool
    # resident, and reads the pool in place (a row-major layout, no copy)
    pcfg, pc = _zamba2_cell()
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(LM(pcfg, attn_impl="naive", remat=None).init, jax.random.key(0)),
    )
    pool = spec((pc.n_layers, pc.num_blocks, pc.block_size, pc.kv_heads, pc.head_dim),
                jnp.dtype(pc.dtype))
    conv = spec((pc.max_seqs, pc.state_layers, pc.conv_width), jnp.float32)
    ssd = spec((pc.max_seqs, pc.state_layers, pc.ssd_heads, pc.ssd_width), jnp.float32)
    B = pc.max_seqs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        compiled = _compile(
            lambda p, *xs: paged_decode_step(p, pcfg, *xs[:6], xs[6:], use_kernel=True),
            params, spec((B, 1), jnp.int32), spec((B, 1), jnp.int32), pool, pool,
            spec((B, pc.max_blocks_per_seq), jnp.int32), spec((B,), jnp.int32), conv, ssd,
            spec((B,), jnp.int32),
        )
    finally:
        jax.clear_caches()
    formats = compiled.input_formats[0]      # (params, tokens, positions, k, v, ...)
    layouts = {name: formats[i].layout.major_to_minor for name, i in
               (("k_pool", 3), ("v_pool", 4), ("conv", 7), ("ssd", 8))}
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nzamba2 paged step: layouts {layouts}; arguments "
              f"{mem.argument_size_in_bytes}, temporaries {mem.temp_size_in_bytes}, "
              f"outputs {mem.output_size_in_bytes} B")
    # the pages and the SSD state row-major: the kernel and the slot writes
    # read them in place (the 25 MB conv state may go layer-major)
    assert all(layouts[n] == tuple(range(len(layouts[n]))) for n in ("k_pool", "v_pool", "ssd"))
    pool_type = f"bf16[{','.join(map(str, pool.shape))}]"
    assert not [l for l in compiled.as_text().splitlines() if " copy(" in l and pool_type in l]


def test_state_write_compiles_in_place(spec):
    _, pc = _zamba2_cell()
    L, S, B = pc.state_layers, pc.max_seqs, pc.max_seqs
    conv = spec((S, L, pc.conv_width), jnp.float32)
    ssd = spec((S, L, pc.ssd_heads, pc.ssd_width), jnp.float32)
    compiled = kv_pool._write_states.lower(
        conv, ssd, spec((B,), jnp.int32), spec((L, B, pc.conv_width), jnp.float32),
        spec((L, B, pc.ssd_heads, pc.ssd_width), jnp.float32)).compile()
    state_bytes = 4 * S * L * (pc.conv_width + pc.ssd_heads * pc.ssd_width)
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes   # both donated
    ssd_type = f"f32[{S},{L},{pc.ssd_heads},{pc.ssd_width}]"
    assert not [l for l in compiled.as_text().splitlines() if " copy(" in l and ssd_type in l]
