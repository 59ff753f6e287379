"""HLO collective parser: synthetic snippets + a real lowered module."""
import jax
import jax.numpy as jnp

from repro.launch import hlo_stats

SNIPPET = """
  %ag = f32[128,256]{1,0} all-gather(f32[8,256]{1,0} %x), replica_groups={}
  %ar.1 = bf16[64]{0} all-reduce(bf16[64]{0} %y), to_apply=%add
  %tup = (f32[32]{0}, f32[16,2]{1,0}) all-reduce-start(f32[32]{0} %a, f32[16,2]{1,0} %b)
  %done = (f32[32]{0}, f32[16,2]{1,0}) all-reduce-done((f32[32]{0}, f32[16,2]{1,0}) %tup)
  %rs = f32[4]{0} reduce-scatter(f32[64]{0} %z), dimensions={0}
  %cp = u8[100]{0} collective-permute(u8[100]{0} %w), source_target_pairs={{0,1}}
"""


def test_parser_counts_and_bytes():
    st = hlo_stats.collective_stats(SNIPPET)
    assert st["all-gather"]["count"] == 1
    assert st["all-gather"]["bytes"] == 128 * 256 * 4
    # -start counted once, -done skipped
    assert st["all-reduce"]["count"] == 2
    assert st["all-reduce"]["bytes"] == 64 * 2 + (32 * 4 + 16 * 2 * 4)
    assert st["reduce-scatter"]["bytes"] == 4 * 4
    assert st["collective-permute"]["bytes"] == 100


def test_parser_on_real_module():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    hlo = jax.jit(lambda a: (a @ a).sum()).lower(x).compile().as_text()
    st = hlo_stats.collective_stats(hlo)  # single device: no collectives
    assert hlo_stats.total_collective_bytes(hlo) == sum(
        v["bytes"] for v in st.values()
    )


def test_scalar_collectives_zero_dims():
    snippet = "%r = f32[] all-reduce(f32[] %x)"
    st = hlo_stats.collective_stats(snippet)
    assert st["all-reduce"]["count"] == 1
    assert st["all-reduce"]["bytes"] == 4
