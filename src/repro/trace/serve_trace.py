"""Record a serving scenario into a ``repro.trace`` op trace.

This is the live half of the golden-trace loop: it builds the exact
engine :mod:`benchmarks.serve_bench` builds for a scenario (same smoke
model, same pool overrides, same watermark maintenance), attaches a
:class:`~repro.trace.record.TraceRecorder`, plays the scenario's
fixed-seed request stream through :func:`repro.serve.loadgen.play`, and
finalizes the trace with the engine's end-of-run totals.

Because every input is seed-pinned, the emitted JSONL is byte-identical
across runs and machines — that is what ``tests/test_trace_golden.py``
asserts against ``tests/goldens/``, and what lets
:func:`repro.trace.replay.replay_trace` re-price the run bit-exactly
without a model or engine in the loop.

Run as a module to (re)generate the golden deliberately::

    PYTHONPATH=src python -m repro.trace.serve_trace \
        --write-golden tests/goldens/steady_smoke.trace.jsonl
"""
from __future__ import annotations

import argparse
from typing import Dict, Optional, Tuple

import numpy as np

from repro.trace.record import DEFAULT_SIM, TraceRecorder

_MODEL_CACHE: Tuple = ()


def _model():
    """Same shared smoke model as ``benchmarks/serve_bench.py``."""
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


def record_scenario(
    name: str = "steady",
    *,
    smoke: bool = True,
    n_requests: Optional[int] = None,
) -> Tuple[TraceRecorder, Dict[str, object]]:
    """Play scenario ``name`` under a recorder; returns (trace, play record).

    ``n_requests`` truncates the scenario's request stream (keeping its
    seeds) — used by fast tests that want a handful of admits rather than
    the whole smoke run.
    """
    from repro.core.kv_pool import KVPoolConfig
    from repro.serve.engine import MaintenanceConfig, ServeEngine
    from repro.serve.loadgen import build_scenario, play

    model, params = _model()
    cfg = model.cfg
    sc = build_scenario(name, smoke=smoke)
    base = dict(
        num_blocks=32, block_size=8, kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        n_layers=cfg.n_layers, max_seqs=4, max_blocks_per_seq=16,
        blocks_per_arena=16, policy="puma", dtype="float32",
    )
    base.update(sc.pool_overrides())
    pool_cfg = KVPoolConfig(**base)
    tile_bytes = (
        2 * pool_cfg.n_layers * pool_cfg.block_size * pool_cfg.kv_heads
        * pool_cfg.head_dim * np.dtype(pool_cfg.dtype).itemsize
    )
    trace = TraceRecorder(
        channels=pool_cfg.n_channels,
        banks_per_channel=8,
        blocks_per_arena=pool_cfg.blocks_per_arena,
        block_bytes=int(tile_bytes),
        sim=dict(DEFAULT_SIM),
        meta={
            "scenario": name,
            "seed": sc.seed,
            "smoke": bool(smoke),
            "model": "stablelm_1_6b.smoke",
            "policy": pool_cfg.policy,
        },
    )
    eng = ServeEngine(
        model, params, pool_cfg, maintenance=MaintenanceConfig(), trace=trace,
    )
    specs = sc.generate()
    if n_requests is not None:
        specs = specs[:n_requests]
    rec = play(eng, specs, max_steps=sc.max_steps)
    trace.finalize(
        clock=eng.clock,
        tokens_decoded=eng.tokens_decoded,
        tokens_prefilled=eng.tokens_prefilled,
        maintenance_ns=eng.maintenance_ns,
    )
    return trace, rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="steady")
    ap.add_argument("--full", action="store_true",
                    help="full-size scenario (default: smoke)")
    ap.add_argument("--write-golden", metavar="PATH", default=None,
                    help="write the canonical JSONL to PATH")
    args = ap.parse_args()
    trace, rec = record_scenario(args.scenario, smoke=not args.full)
    if args.write_golden:
        trace.write(args.write_golden)
        print(f"[serve_trace] wrote {args.write_golden} "
              f"({len(trace.events)} events)")
    else:
        print(f"[serve_trace] {args.scenario}: {len(trace.events)} events, "
              f"done={rec['done']}/{rec['submitted']}")


if __name__ == "__main__":
    main()
