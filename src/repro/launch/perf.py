import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# Performance hillclimbing (EXPERIMENTS.md §Perf).
#
# Runs named experiment variants against a cell, recomputes the three
# roofline terms via the same unrolled-extrapolation pipeline, and appends
# hypothesis -> change -> before/after -> verdict records to
# experiments/perf_log.json.
#
#   PYTHONPATH=src python -m repro.launch.perf --cell stablelm_1_6b/train_4k \
#       --variant pure_dp
import argparse
import json
from typing import Any, Dict, Optional

from repro.configs.base import SHAPES
from repro.configs.registry import get_config
from repro.launch import roofline as RL
from repro.launch.dryrun import run_cell

PERF_LOG = "experiments/perf_log.json"


#: named experiment variants: kwargs passed to run_cell (rules = activation
#: rule overrides, param_rules = parameter sharding overrides, ...).
VARIANTS: Dict[str, Dict[str, Any]] = {
    "baseline": {},
    # pure data parallelism: re-purpose the "model" axis as extra FSDP width;
    # no TP -> no per-layer activation all-reduces, weights ZeRO-3 over 256.
    "pure_dp": {
        "rules": {
            "batch": ("data", "model"), "seq_res": None, "heads": None,
            "kv_heads": None, "mlp": None, "vocab": None, "moe_t": None,
            "moe_cap": None, "moe_flat": None,
        },
        "param_rules": {
            "vocab": None, "embed": ("data", "model"), "embed_tp": None,
            "heads": None, "kv_heads": None, "mlp": None,
        },
        "accum_steps": 1,
    },
    # half-TP: model axis split 2-way TP x 8-way extra DP is not expressible
    # on a fixed mesh; instead keep TP but turn off sequence parallelism.
    "no_sp": {"rules": {"seq_res": None}},
    # remat policy: save matmul outputs (no forward recompute in backward)
    "remat_dots": {"remat": "dots"},
    "remat_none": {"remat": "none"},
    # un-fused attention baseline (what you lose without flash)
    "naive_attn": {"attn_impl": "naive"},
    # MoE: tighter capacity
    "cap_1_0": {"cfg_overrides": {"moe_capacity_factor": 1.0}},
    # decode: bf16 KV (undo the fp8 default) for A/B
    "kv_bf16": {"cfg_overrides": {"kv_cache_dtype": "bfloat16"}},
    # decode: fp8 KV cache
    "kv_fp8": {"cfg_overrides": {"kv_cache_dtype": "float8_e4m3fn"}},
    # combos
    "pure_dp_dots": {
        "rules": {
            "batch": ("data", "model"), "seq_res": None, "heads": None,
            "kv_heads": None, "mlp": None, "vocab": None, "moe_t": None,
            "moe_cap": None, "moe_flat": None,
        },
        "param_rules": {
            "vocab": None, "embed": ("data", "model"), "embed_tp": None,
            "heads": None, "kv_heads": None, "mlp": None,
        },
        "accum_steps": 1,
        "remat": "dots",
    },
}


def measure(arch: str, shape_name: str, variant: str) -> Dict[str, Any]:
    kw = dict(VARIANTS[variant])
    cfg = get_config(arch)
    # reuse the roofline extrapolation but with variant kwargs
    base_kw = dict(
        attn_impl=kw.pop("attn_impl", "chunked"),
        scan_layers=False, multi_pod=False,
        accum_steps=kw.pop("accum_steps", 1),
        remat=kw.pop("remat", "full"),
        **kw,
    )

    if cfg.family == "hybrid":
        fs = [RL._pd(run_cell(arch, shape_name, n_layers=n, **base_kw))
              for n in RL.hybrid_depths(cfg)]
        tot = RL.hybrid_terms(*fs, cfg)["total"]
    else:
        f1 = RL._pd(run_cell(arch, shape_name, n_layers=1, **base_kw))
        f2 = RL._pd(run_cell(arch, shape_name, n_layers=2, **base_kw))
        co = RL._lin2(f1, f2)
        tot = {m: co["A"][m] + cfg.n_layers * co["B"][m] for m in RL.METRICS}

    mf = RL.model_flops_per_device(arch, shape_name)
    terms = {
        "compute_s": tot["flops"] / RL.PEAK_FLOPS,
        "memory_s": tot["fused_bytes"] / RL.HBM_BW,
        "collective_s": tot["collective_bytes"] / RL.ICI_BW,
    }
    bound = max(terms.values())
    # peak memory check at full scale (scanned compile)
    full = run_cell(arch, shape_name, False, scan_layers=True, **{
        k: v for k, v in base_kw.items()
        if k not in ("scan_layers", "multi_pod")
    })
    return {
        "arch": arch, "shape": shape_name, "variant": variant,
        **terms,
        "dominant": max(terms, key=terms.get),
        "useful_flops_ratio": mf["model_flops_per_device"] / tot["flops"],
        "roofline_fraction": (mf["model_flops_per_device"] / RL.PEAK_FLOPS) / bound,
        "peak_bytes_full": full["per_device"]["peak_bytes"],
        "totals_per_device": tot,
    }


def log_experiment(rec: Dict[str, Any], hypothesis: str = "") -> None:
    try:
        with open(PERF_LOG) as f:
            log = json.load(f)
    except (OSError, json.JSONDecodeError):
        log = []
    rec = dict(rec)
    if hypothesis:
        rec["hypothesis"] = hypothesis
    log.append(rec)
    os.makedirs(os.path.dirname(PERF_LOG) or ".", exist_ok=True)
    with open(PERF_LOG, "w") as f:
        json.dump(log, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, help="arch/shape")
    ap.add_argument("--variant", required=True, choices=sorted(VARIANTS))
    ap.add_argument("--hypothesis", default="")
    args = ap.parse_args()
    arch, shape_name = args.cell.split("/")
    arch = arch.replace("-", "_")
    rec = measure(arch, shape_name, args.variant)
    log_experiment(rec, args.hypothesis)
    print(json.dumps({k: v for k, v in rec.items() if k != "totals_per_device"},
                     indent=1))


if __name__ == "__main__":
    main()
