"""Benchmark harness — one module per paper table/figure, plus the
aggregator that folds every persisted ``BENCH_*.json`` into one summary.

  * alloc_fraction  — paper §1 motivation (PUD-executable fraction,
                      now also per-channel)
  * microbench      — paper Figure 2 (zero/copy/aand speedups vs malloc)
  * kv_pool_bench   — TPU adaptation (block-table contiguity per policy)
  * kernel_bench    — kernel reference-path timings + agreement
  * translate_bench — vectorized translation/planning fast path vs the seed
                      scalar algorithms (persists BENCH_translate.json)
  * channel_bench   — multi-channel PUD scaling + controller contention
                      (persists BENCH_channels.json)
  * chaos_bench     — degraded-mode metrics under the fixed-seed fault
                      plan (persists BENCH_faults.json)
  * churn_bench     — long-horizon aging: executable-fraction decay per
                      allocator + watermark compaction recovery + journal
                      crash/replay (persists BENCH_churn.json)
  * serve_bench     — open-loop serving load scenarios through ServeEngine
                      (traffic generators + tenant mixes; persists
                      BENCH_serve.json)
  * trace_bench     — GEMV/MoE decode offload fractions per allocator +
                      channel-striped makespan + serve-trace replay verdict
                      (persists BENCH_trace.json)

Prints ``name,us_per_call,derived`` CSV rows.  ``--smoke`` shrinks the
persisted microbenchmarks for CI; ``--only translate`` runs just one
module.  After the selected modules run, every ``BENCH_*.json`` found in
the working directory is folded into ``BENCH_summary.json`` under the
shared record schema ``{bench, name, speedup, seconds, config}``
(missing fields null); ``--aggregate-only`` skips the benchmarks and only
rebuilds the summary from whatever JSON files already exist.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List

SUMMARY_PATH = "BENCH_summary.json"


def aggregate(pattern: str = "BENCH_*.json") -> List[Dict]:
    """Fold every persisted benchmark file into shared-schema records.

    Each source file maps record names to dicts with (a subset of) the
    shared fields; anything non-dict (e.g. a ``config`` block) is carried
    into the records of its file as ``config`` context.
    """
    rows: List[Dict] = []
    for path in sorted(glob.glob(pattern)):
        if os.path.basename(path) == SUMMARY_PATH:
            continue
        bench = os.path.basename(path)[len("BENCH_"):-len(".json")]
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"[aggregate] skipping {path}: {e}", file=sys.stderr)
            continue
        shared_cfg = data.get("config") if isinstance(data, dict) else None
        if not isinstance(data, dict):
            continue
        for name, rec in data.items():
            if name == "config" or not isinstance(rec, dict):
                continue
            rows.append({
                "bench": bench,
                "name": name,
                "n": rec.get("n"),
                "speedup": rec.get("speedup"),
                "seconds": rec.get("seconds"),
                "config": rec.get("config", shared_cfg),
            })
    return rows


def write_summary(rows: List[Dict]) -> None:
    with open(SUMMARY_PATH, "w") as f:
        json.dump({"records": rows}, f, indent=1, sort_keys=True)
    benches = sorted({r["bench"] for r in rows})
    print(
        f"[aggregate] {len(rows)} records from {len(benches)} benchmarks "
        f"({', '.join(benches)}) -> {SUMMARY_PATH}"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="reduced sizes (CI)")
    ap.add_argument("--only", default=None,
                    help="run a single module (e.g. 'translate')")
    ap.add_argument("--aggregate-only", action="store_true",
                    help="skip benchmarks; rebuild BENCH_summary.json")
    args = ap.parse_args()

    if not args.aggregate_only:
        from benchmarks import (
            alloc_fraction,
            channel_bench,
            chaos_bench,
            churn_bench,
            kernel_bench,
            kv_pool_bench,
            microbench,
            serve_bench,
            trace_bench,
            translate_bench,
        )

        print("name,us_per_call,derived")

        def emit(name: str, us: float, derived) -> None:
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()

        modules = {
            "alloc_fraction": lambda: alloc_fraction.run(emit),
            "microbench": lambda: microbench.run(emit),
            "kv_pool": lambda: kv_pool_bench.run(emit),
            "kernel": lambda: kernel_bench.run(emit),
            "translate": lambda: translate_bench.run(emit, smoke=args.smoke),
            "channels": lambda: channel_bench.run(emit, smoke=args.smoke),
            "chaos": lambda: chaos_bench.run(emit, smoke=args.smoke),
            "churn": lambda: churn_bench.run(emit, smoke=args.smoke),
            "serve": lambda: serve_bench.run(emit, smoke=args.smoke),
            "trace": lambda: trace_bench.run(emit, smoke=args.smoke),
        }
        selected = {
            name: fn
            for name, fn in modules.items()
            if args.only is None or args.only in name
        }
        if not selected:
            raise SystemExit(
                f"--only {args.only!r} matches no module ({', '.join(modules)})"
            )
        for fn in selected.values():
            fn()

    write_summary(aggregate())


if __name__ == "__main__":
    main()
