"""Production-scale serving load benchmark (ISSUE 9 tentpole).

Drives the fixed-seed scenario registry (:mod:`repro.serve.loadgen`)
through :class:`~repro.serve.engine.ServeEngine` — open-loop arrivals, so
queue delay is measured rather than hidden — and persists one record per
scenario into ``BENCH_serve.json``:

* ``scenario/steady``       — fixed-rate baseline (1 request / 2 ticks)
* ``scenario/bursty``       — 8-request thundering herds, ~24-tick gaps
* ``scenario/long_context`` — prompt-heavy Poisson traffic near the
                              per-sequence block ceiling
* ``scenario/multi_tenant`` — registry-derived tenant mix (stablelm /
                              chatglm3 / granite_34b) on a 2-channel
                              striped pool
* ``scenario/cancel_heavy`` — 45% client cancellations + engine deadlines

Each record carries tokens/s (against the deterministic
:class:`~repro.serve.loadgen.SimCost` time model), p50/p99 queue and
completion latency in engine ticks, pool occupancy (mean/peak), live
block-table contiguity (the paper's PUD-executable-fraction analogue,
time-averaged over loaded steps), per-channel balance, and the
degraded-mode ledger (rejected / cancelled / preemptions / compactions).

Everything in the JSON is a pure function of the scenario seeds, so a
rerun is byte-identical — ``--gate`` runs the whole set twice and asserts
exactly that (plus ledger conservation and metric sanity); wall-clock
timings go to stdout only.  ``run(emit)`` plugs into ``benchmarks/run.py``
(``--smoke`` shrinks request counts; full mode streams ~1800 requests).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict, Tuple

OUT_PATH = "BENCH_serve.json"

_MODEL_CACHE: Tuple = ()


def _model():
    """Build the smoke serving model once per process (scenarios share it)."""
    global _MODEL_CACHE
    if not _MODEL_CACHE:
        import jax

        from repro.configs.registry import get_config
        from repro.models.transformer import LM

        cfg = get_config("stablelm_1_6b").smoke()
        model = LM(cfg, attn_impl="naive", remat=None)
        params = model.init(jax.random.key(0))
        _MODEL_CACHE = (model, params)
    return _MODEL_CACHE


def make_engine(scenario):
    """Engine for one scenario: shared smoke model + the scenario's pool
    overrides, with watermark maintenance on so compaction competes with
    live traffic (the whole point of load-testing it)."""
    from repro.core.kv_pool import KVPoolConfig
    from repro.serve.engine import MaintenanceConfig, ServeEngine

    model, params = _model()
    cfg = model.cfg
    base = dict(
        num_blocks=32, block_size=8, kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        n_layers=cfg.n_layers, max_seqs=4, max_blocks_per_seq=16,
        blocks_per_arena=16, policy="puma", dtype="float32",
    )
    base.update(scenario.pool_overrides())
    return ServeEngine(
        model, params, KVPoolConfig(**base), maintenance=MaintenanceConfig(),
    )


def run_scenario(name: str, smoke: bool) -> Tuple[Dict, float]:
    """One scenario end to end; returns (record, wall_seconds) — wall time
    is never persisted (the JSON must be byte-reproducible)."""
    from repro.robustness import check_engine
    from repro.serve.loadgen import build_scenario, play

    sc = build_scenario(name, smoke=smoke)
    eng = make_engine(sc)
    specs = sc.generate()
    t0 = time.perf_counter()
    rec = play(eng, specs, max_steps=sc.max_steps)
    wall = time.perf_counter() - t0
    check_engine(eng).assert_ok()
    rec["scenario"] = {
        "seed": sc.seed,
        "arrival": sc.arrival.kind,
        "tenants": [t.name for t in sc.tenants],
        "pool": sc.pool_overrides(),
        "description": sc.description,
    }
    return rec, wall


def bench(smoke: bool = False) -> Tuple[Dict, Dict[str, float]]:
    from repro.serve.loadgen import SCENARIO_NAMES

    results: Dict[str, Dict] = {}
    walls: Dict[str, float] = {}
    for name in SCENARIO_NAMES:
        rec, wall = run_scenario(name, smoke)
        results[f"scenario/{name}"] = rec
        walls[name] = wall
    results["config"] = {
        "model": "stablelm_1_6b.smoke",
        "scenarios": list(SCENARIO_NAMES),
        "smoke": smoke,
        "time_model": "SimCost (deterministic; wall clock not persisted)",
    }
    return results, walls


def _canon(results: Dict) -> str:
    return json.dumps(results, indent=1, sort_keys=True)


def check(results: Dict) -> None:
    """The gate's per-scenario assertions (also run by scripts/ci.sh)."""
    from repro.serve.loadgen import SCENARIO_NAMES

    for name in SCENARIO_NAMES:
        rec = results[f"scenario/{name}"]
        assert rec["conservation_ok"], (name, "ledger leaked requests")
        assert rec["done"] > 0, (name, "nothing completed")
        assert rec["tokens_per_s"] > 0, (name, "no throughput")
        assert 0.0 <= rec["occupancy_mean"] <= rec["occupancy_peak"] <= 1.0, name
        assert 0.0 < rec["contiguity"] <= 1.0, (name, rec["contiguity"])
        if rec["p50_complete_steps"] is not None:
            assert rec["p50_complete_steps"] <= rec["p99_complete_steps"], name
        if rec["p50_queue_steps"] is not None:
            assert rec["p50_queue_steps"] <= rec["p99_queue_steps"], name
    # scenario-shape signatures: bursts queue deeper than the steady drip,
    # the cancellation mix actually cancels, the tenant mix actually mixes.
    assert (results["scenario/bursty"]["queue_depth_peak"]
            > results["scenario/steady"]["queue_depth_peak"])
    assert results["scenario/bursty"]["preemptions"] > 0, \
        "bursty pool never overcommitted — preemption path unexercised"
    assert results["scenario/cancel_heavy"]["cancelled"] > 0
    mt = results["scenario/multi_tenant"]
    assert mt["channels"] == 2
    assert sum(1 for v in mt["done_by_tenant"].values() if v > 0) >= 2


def run(emit: Callable[[str, float, float], None], smoke: bool = False,
        gate: bool = False) -> Dict:
    """benchmarks/run.py hook: emit CSV rows + persist BENCH_serve.json."""
    results, walls = bench(smoke=smoke)
    if gate:
        rerun, _ = bench(smoke=smoke)
        results["determinism"] = {
            "identical": _canon(results) == _canon(rerun),
            "reruns": 2,
        }
        check(results)
        assert results["determinism"]["identical"], \
            "fixed-seed rerun diverged from the first pass"
    for name, wall in walls.items():
        rec = results[f"scenario/{name}"]
        emit(f"serve/{name}", 1e6 * wall, rec["tokens_per_s"])
    with open(OUT_PATH, "w") as f:
        f.write(_canon(results))
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="fast CI mode")
    ap.add_argument("--gate", action="store_true",
                    help="rerun the full set and assert byte-identical + sane")
    args = ap.parse_args()
    results = run(lambda n, us, d: print(f"{n},{us:.1f},{d}"),
                  smoke=args.smoke, gate=args.gate)
    print(f"[serve_bench] wrote {OUT_PATH}")
    for key, rec in results.items():
        if not key.startswith("scenario/"):
            continue
        print(
            f"  {key.split('/', 1)[1]:<13} done={rec['done']:>4}/{rec['submitted']:<4} "
            f"tok/s={rec['tokens_per_s']:>10.1f} "
            f"p50/p99={rec['p50_complete_steps']}/{rec['p99_complete_steps']} "
            f"occ={rec['occupancy_mean']:.2f} contig={rec['contiguity']:.3f} "
            f"cancel={rec['cancelled']} preempt={rec['preemptions']}"
        )
    if "determinism" in results:
        print(f"  deterministic: {results['determinism']['identical']}")


if __name__ == "__main__":
    main()
