"""Compaction engine (ISSUE 8): planning, pricing, bit-exact execution,
and the serving engine's watermark maintenance hook."""
import random

import numpy as np
import pytest

from repro.core.allocators import PhysicalMemory
from repro.core.arena import TilePool
from repro.core.dram import AddressMap, DramGeometry
from repro.core.puma import PumaAllocator
from repro.robustness import (
    JournalReplayError,
    check_allocator,
    check_kv_pool,
    check_tile_pool,
)
from repro.robustness.compaction import (
    compact_allocator,
    compact_pool,
    plan_allocator_compaction,
    plan_pool_compaction,
)

pytestmark = pytest.mark.churn


def hyp_seeds(func):
    """Hypothesis-driven seeds when installed, fixed seeds otherwise."""
    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        return pytest.mark.parametrize("seed", [0xC0FFEE, 0xBADF00D])(func)
    return settings(max_examples=2, deadline=None)(
        given(seed=st.integers(0, 2**32 - 1))(func)
    )


AMAP = AddressMap(
    DramGeometry(channels=4, subarrays_per_bank=16, rows_per_subarray=32)
)
REGION = AMAP.region_bytes


def _aged_allocator(seed, cycles=1500, journal=None, phys=None):
    """Churn a small PUD pool to ~90 % utilization; optionally shadow the
    bytes so compaction's data movement can be audited."""
    mem = PhysicalMemory(AMAP, seed=7, n_huge_pages=4)
    pa = PumaAllocator(mem, journal=journal)
    pa.pim_preallocate(4)
    total = pa.free_regions()
    rng = random.Random(seed)
    data_rng = np.random.default_rng(seed)
    expected = {}

    def fill(a):
        if phys is None:
            return
        n = sum(e.nbytes for e in a.extents)
        data = data_rng.integers(0, 256, n, dtype=np.uint8)
        for e in a.extents:
            phys[e.pa:e.pa + e.nbytes] = data[e.va_off:e.va_off + e.nbytes]
        expected[a.va] = data

    live = []
    for _ in range(cycles):
        if live and (pa.free_regions() < total // 10 or rng.random() < 0.45):
            victim = live.pop(rng.randrange(len(live)))
            expected.pop(victim.va, None)
            pa.pim_free(victim)
        else:
            a = pa.pim_alloc(rng.randint(REGION // 2, 4 * REGION))
            if a is not None:
                live.append(a)
                fill(a)
                b = pa.pim_alloc_align(a.size, a)
                if b is not None:
                    live.append(b)
                    fill(b)
    return pa, live, expected


def _read_back(phys, a):
    return np.concatenate([
        phys[e.pa:e.pa + e.nbytes]
        for e in sorted(a.extents, key=lambda e: e.va_off)
    ])


@hyp_seeds
def test_allocator_compaction_concentrates_and_is_bit_exact(seed):
    phys = np.zeros(AMAP.total_bytes, np.uint8)
    pa, live, expected = _aged_allocator(seed, phys=phys)
    frag_before = pa.fragmentation()
    rep = compact_allocator(pa, phys=phys)
    check_allocator(pa).assert_ok()
    if rep.executed:
        assert rep.frag_after < frag_before
        assert rep.cost is not None and rep.cost.total_ns > 0
        # allocator-level moves always cross subarrays: CPU-priced
        assert rep.rowclone_rows == 0 and rep.cpu_rows == rep.executed
    for a in live:
        assert np.array_equal(_read_back(phys, a), expected[a.va]), hex(a.va)
    # translation still agrees with the extents after the remap
    for a in live[:8]:
        assert a.pa_of(0) == a.extents[0].pa


@hyp_seeds
def test_allocator_compaction_idempotent_and_conserves(seed):
    """Repeated blacklist remaps + compaction passes keep conservation
    (preallocated == free + in_use + quarantined, audited by
    check_allocator) and converge: a second pass over an already-compacted
    pool plans nothing new."""
    pa, live, _ = _aged_allocator(seed)
    # free down to ~50 % so the blacklist remap has spare capacity
    for a in live[len(live) // 2:]:
        pa.pim_free(a)
    del live[len(live) // 2:]
    # one permanent-fault remap in the mix, applied twice: the second
    # application must be a no-op (the subarray is already drained)
    sa = int(AMAP.region_subarrays(
        np.asarray([live[0].extents[0].pa], np.int64))[0])
    pa.blacklist_subarray(sa)
    check_allocator(pa).assert_ok()
    assert pa.blacklist_subarray(sa) == 0      # idempotent
    check_allocator(pa).assert_ok()

    rep1 = compact_allocator(pa)
    check_allocator(pa).assert_ok()
    rep2 = compact_allocator(pa)
    check_allocator(pa).assert_ok()
    assert rep2.frag_after <= rep1.frag_after + 1e-9
    # convergence: once free capacity is concentrated, replanning is empty
    rep3 = compact_allocator(pa)
    assert rep3.executed == 0 or rep3.frag_after <= rep2.frag_after
    for a in live:
        pa.pim_free(a)
    check_allocator(pa).assert_ok()


def test_allocator_stale_plan_raises():
    pa, live, _ = _aged_allocator(0xBEEF)
    plan = plan_allocator_compaction(pa)
    if not plan.moves:
        pytest.skip("churn produced an unfragmented pool")
    # consume the plan's destination region behind its back
    dst = plan.moves[0].dst
    sa = int(AMAP.region_subarrays(np.asarray([dst], np.int64))[0])
    assert pa._ordered.take_specific(sa, dst)
    with pytest.raises(JournalReplayError):
        compact_allocator(pa, plan)


def test_pool_run_repair_is_rowclone_priced():
    pool = TilePool(1, 16, "puma")     # one arena: collisions guaranteed
    a = pool.alloc(2)
    b = pool.alloc(2)          # occupies the slots right after a
    pool.extend(a, 2)          # a's tiles fracture around b
    assert a.contiguous_run_fraction() < 1.0
    pool.free(b)               # the gap is free: run repair can re-knit it
    plan = plan_pool_compaction(pool)
    assert plan.rowclone_moves, "expected intra-arena run-repair moves"
    before = a.contiguous_run_fraction()
    rep = compact_pool(pool, plan)
    check_tile_pool(pool).assert_ok()
    assert a.contiguous_run_fraction() >= before
    assert rep.rowclone_rows == len(plan.rowclone_moves)


@hyp_seeds
def test_pool_compaction_under_churn(seed):
    pool = TilePool(8, 32, "puma")
    rng = random.Random(seed)
    live = []
    for _ in range(2000):
        roll = rng.random()
        if live and roll < 0.40:
            pool.free(live.pop(rng.randrange(len(live))))
        elif live and roll < 0.55:
            pool.extend(rng.choice(live), 1)
        else:
            h = pool.alloc(rng.randint(1, 8))
            if h is not None:
                live.append(h)
    owned_before = sorted(
        (h.hid, len(h.tiles)) for h in live
    )
    contig_before = float(np.mean(
        [h.contiguous_run_fraction() for h in live]
    )) if live else 1.0
    rep = compact_pool(pool)
    check_tile_pool(pool).assert_ok()
    assert sorted((h.hid, len(h.tiles)) for h in live) == owned_before
    if rep.executed:
        contig_after = float(np.mean(
            [h.contiguous_run_fraction() for h in live]
        ))
        assert contig_after >= contig_before - 1e-9
    # repeated passes stay safe and never give back handle contiguity
    # (run repair may trade free-run fragmentation for it, so the frag
    # metric alone is not monotone)
    compact_pool(pool)
    check_tile_pool(pool).assert_ok()
    assert sorted((h.hid, len(h.tiles)) for h in live) == owned_before
    if live:
        assert float(np.mean(
            [h.contiguous_run_fraction() for h in live]
        )) >= contig_before - 1e-9


def test_kv_compact_moves_data_bit_exactly():
    import jax.numpy as jnp

    from repro.core.kv_pool import KVPoolConfig, PagedKVPool

    cfg = KVPoolConfig(num_blocks=64, block_size=4, kv_heads=2, head_dim=8,
                       n_layers=2, max_seqs=16, max_blocks_per_seq=16,
                       blocks_per_arena=16, policy="puma", dtype="float32")
    kv = PagedKVPool(cfg)
    rng = np.random.default_rng(11)
    slots = [kv.admit(int(rng.integers(3, 13))) for _ in range(10)]
    for s in slots[::2]:
        kv.release(s)
    slots = slots[1::2] + [kv.admit(int(rng.integers(8, 20))) for _ in range(3)]
    slots = [s for s in slots if s is not None]
    # stamp every live block through the *layer-folded* index space
    tags = {}
    for s in slots:
        h, _ = kv._seqs[s]
        tg = rng.standard_normal(len(h.tiles)).astype(np.float32)
        tags[s] = tg
        for li in range(cfg.n_layers):
            kv.k = kv.k.at[li, jnp.asarray(h.tiles), 0, 0, 0].set(
                jnp.asarray(tg * (li + 1))
            )
    rep = kv.compact(max_moves=64)
    check_kv_pool(kv).assert_ok()
    if rep is None:
        pytest.skip("nothing to compact")
    for s in slots:
        h, _ = kv._seqs[s]
        for li in range(cfg.n_layers):
            got = np.asarray(kv.k[li, jnp.asarray(h.tiles), 0, 0, 0])
            assert np.allclose(got, tags[s] * (li + 1)), (s, li)


def test_engine_maintenance_hook_fires_and_preserves_output():
    import jax

    from repro.configs.registry import get_config
    from repro.core.kv_pool import KVPoolConfig
    from repro.models.transformer import LM
    from repro.serve.engine import MaintenanceConfig, Request, ServeEngine

    cfg = get_config("stablelm_1_6b").smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))

    def pool_cfg():
        return KVPoolConfig(
            num_blocks=64, block_size=8, kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd, n_layers=cfg.n_layers, max_seqs=8,
            max_blocks_per_seq=16, blocks_per_arena=16, policy="puma",
            dtype="float32",
        )

    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, cfg.vocab_size, 9)) for _ in range(4)]

    def drive(maint):
        eng = ServeEngine(model, params, pool_cfg(), maintenance=maint)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=list(p), max_new=6))
        done = eng.run()
        return eng, {r.rid: r.out for r in done}

    _, base_out = drive(None)
    eng, out = drive(MaintenanceConfig(
        free_low=0.9, frag_high=0.05, contig_low=0.999,
        max_moves=64, every=2,
    ))
    m = eng.metrics()
    assert m["compaction_passes"] > 0
    assert m["blocks_migrated"] > 0
    assert m["maintenance_ns"] > 0
    assert out == base_out          # compaction never changes generation
    # the rate limiter actually limits
    assert eng.compaction_passes <= eng.clock // 2 + 1
