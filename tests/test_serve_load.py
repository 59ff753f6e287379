"""Serving load-harness suite (ISSUE 9): scenarios through the real engine.

Marked ``serve`` — the CI gate runs this suite plus the fixed-seed
``benchmarks/serve_bench.py --smoke --gate`` pass.  Three anchors:

* **Conservation** — after an open-loop run drains, every submitted request
  is accounted for: ``submitted == done + rejected + cancelled`` and the
  pool is back to fully free.
* **Determinism** — the same seeded scenario through two fresh engines
  yields the *same metrics record*, byte for byte.
* **Schema** — the key names/types of ``ServeEngine.metrics()``,
  ``channel_occupancy()``, ``stall_report()`` and ``step_sample()`` are
  pinned, because ``BENCH_serve.json`` and the CI gate read them by name.
"""
import json

import jax
import pytest

from repro.configs.registry import get_config
from repro.core.kv_pool import KVPoolConfig
from repro.models.transformer import LM
from repro.robustness import check_engine
from repro.serve.engine import MaintenanceConfig, Request, ServeEngine
from repro.serve.loadgen import build_scenario, play

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("stablelm_1_6b").smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))
    return model, params


def _engine(model_and_params, overrides=()):
    model, params = model_and_params
    cfg = model.cfg
    base = dict(
        num_blocks=32, block_size=8, kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        n_layers=cfg.n_layers, max_seqs=4, max_blocks_per_seq=16,
        blocks_per_arena=16, policy="puma", dtype="float32",
    )
    base.update(dict(overrides))
    return ServeEngine(
        model, params, KVPoolConfig(**base), maintenance=MaintenanceConfig(),
    )


def _run_scenario(model_and_params, name):
    sc = build_scenario(name, smoke=True)
    eng = _engine(model_and_params, sc.pool)
    rec = play(eng, sc.generate(), max_steps=sc.max_steps)
    return eng, rec


# ---------------------------------------------------------------------------
# conservation + sanity under load
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["bursty", "cancel_heavy"])
def test_open_loop_run_conserves_the_request_ledger(model_and_params, name):
    eng, rec = _run_scenario(model_and_params, name)
    assert rec["conservation_ok"]
    assert rec["submitted"] == rec["n"]
    assert rec["submitted"] == rec["done"] + rec["rejected"] + rec["cancelled"]
    assert not eng.queue and not eng.live
    assert eng.pool.pool.free_tiles() == eng.pool.pool.total_tiles
    check_engine(eng).assert_ok()


def test_bursty_scenario_exercises_preemption_and_recompute(model_and_params):
    eng, rec = _run_scenario(model_and_params, "bursty")
    assert rec["preemptions"] > 0
    assert rec["done"] == rec["n"]          # recompute-on-resume finished all
    assert rec["queue_depth_peak"] > 0      # open loop measured the herd


def test_cancel_heavy_scenario_actually_cancels(model_and_params):
    _, rec = _run_scenario(model_and_params, "cancel_heavy")
    assert rec["cancelled"] > 0
    assert rec["done"] > 0                  # but not everything dies


def test_metric_record_sanity(model_and_params):
    _, rec = _run_scenario(model_and_params, "steady")
    assert rec["tokens"] > 0 and rec["tokens_per_s"] > 0
    assert 0.0 <= rec["occupancy_mean"] <= rec["occupancy_peak"] <= 1.0
    assert 0.0 < rec["contiguity_min"] <= rec["contiguity"] <= 1.0
    assert rec["p50_queue_steps"] <= rec["p99_queue_steps"]
    assert rec["p50_complete_steps"] <= rec["p99_complete_steps"]
    assert rec["sim_ns"] > 0
    json.dumps(rec)                          # the whole record is JSON-clean


def test_fixed_seed_scenario_is_deterministic(model_and_params):
    _, a = _run_scenario(model_and_params, "steady")
    _, b = _run_scenario(model_and_params, "steady")
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_client_cancel_mid_decode_releases_the_slot(model_and_params):
    eng = _engine(model_and_params)
    eng.submit(Request(rid=0, prompt=[1, 2, 3, 4], max_new=6))
    eng.step()                               # prefill + first decode
    assert eng.cancel(0)
    assert not eng.live and len(eng.cancelled) == 1
    assert eng.cancel(0) is False            # idempotent: already finished
    assert eng.pool.pool.free_tiles() == eng.pool.pool.total_tiles
    eng.drain()
    assert eng.submitted == 1 and len(eng.cancelled) == 1


# ---------------------------------------------------------------------------
# schema pins (satellite: BENCH_serve.json + the CI gate read these by name)
# ---------------------------------------------------------------------------

def _loaded_engine(model_and_params):
    eng = _engine(model_and_params)
    eng.submit(Request(rid=0, prompt=[5, 6, 7], max_new=3))
    eng.step()
    return eng


METRICS_KEYS = {
    "mean_contiguous_fraction", "descriptors_per_tile", "live_seqs",
    "channels", "channel_balance", "clock", "steps", "tokens",
    "tokens_prefilled", "submitted", "done", "queue_depth", "used_fraction",
    "frag", "align_hits", "align_misses", "rejected", "cancelled",
    "preemptions", "injected_misses", "maintenance_ns", "compaction_passes",
    "blocks_migrated", "weights_in_compute_dtype",
}

STEP_SAMPLE_KEYS = {
    "contiguity", "descriptors_per_tile", "channel_balance", "clock",
    "steps", "live", "queued", "free_tiles", "used_fraction",
    "tokens_decoded", "tokens_prefilled", "done", "rejected", "cancelled",
    "preemptions",
}

STALL_REPORT_KEYS = {
    "clock", "steps", "queued", "live", "free_tiles", "total_tiles",
    "free_slots", "done", "rejected", "cancelled", "preemptions",
}


def test_metrics_schema_is_pinned(model_and_params):
    met = _loaded_engine(model_and_params).metrics()
    assert set(met) == METRICS_KEYS
    assert all(isinstance(v, float) for v in met.values()), {
        k: type(v) for k, v in met.items() if not isinstance(v, float)
    }


def test_step_sample_schema_is_pinned(model_and_params):
    sample = _loaded_engine(model_and_params).step_sample()
    assert set(sample) == STEP_SAMPLE_KEYS
    assert all(isinstance(v, float) for v in sample.values())


def test_stall_report_schema_is_pinned(model_and_params):
    eng = _engine(model_and_params)
    eng.submit(Request(rid=0, prompt=[1, 2], max_new=2))
    rep = eng.stall_report()
    assert set(rep) == STALL_REPORT_KEYS
    assert isinstance(rep["queued"], list)
    assert set(rep["queued"][0]) == {"rid", "blocks_needed", "preemptions"}
    for k in STALL_REPORT_KEYS - {"queued"}:
        assert isinstance(rep[k], int), k


def test_channel_occupancy_schema_is_pinned(model_and_params):
    eng = _loaded_engine(model_and_params)
    occ = eng.channel_occupancy()
    assert set(occ) == {"channels", "used_tiles", "free_tiles", "balance"}
    assert isinstance(occ["channels"], int)
    assert isinstance(occ["balance"], float)
    assert len(occ["used_tiles"]) == len(occ["free_tiles"]) == occ["channels"]
    assert sum(occ["used_tiles"]) > 0        # one live sequence holds tiles


# ---------------------------------------------------------------------------
# opt-in full-size lane (scripts/ci.sh --full): the production-scale
# trajectory, not the smoke shrink
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("name", ["steady", "bursty"])
def test_full_size_scenario_trajectory(model_and_params, name):
    """Full (non-smoke) scenario through the engine: hundreds of requests
    per scenario (the whole registry streams ~1800 across the five), with
    the same ledger-conservation and drain invariants as the smoke lane."""
    sc = build_scenario(name, smoke=False)
    eng = _engine(model_and_params, sc.pool)
    rec = play(eng, sc.generate(), max_steps=sc.max_steps)
    assert rec["n"] >= 10 * build_scenario(name, smoke=True).generate().__len__()
    assert rec["conservation_ok"]
    assert rec["submitted"] == rec["done"] + rec["rejected"] + rec["cancelled"]
    assert not eng.queue and not eng.live
    assert eng.pool.pool.free_tiles() == eng.pool.pool.total_tiles
    assert rec["tokens_per_s"] > 0
