import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# Roofline analysis (EXPERIMENTS.md §Roofline).
#
# Terms per (arch x shape) on the single-pod mesh, all PER DEVICE per step
# (cost_analysis of the partitioned module is per-device — calibrated in
# EXPERIMENTS.md §Methodology):
#
#   compute_s    = HLO_flops / peak_flops          (197 TFLOP/s bf16, v5e)
#   memory_s     = HLO_bytes_accessed / hbm_bw     (819 GB/s)
#   collective_s = collective_bytes / ici_bw       (50 GB/s/link)
#
# XLA counts a lax.scan body ONCE, so scanned-layer models under-report.
# We recover exact totals by compiling small UNROLLED variants and solving
# the linear model  F(L) = A + L*B  (dense/moe/ssm/vlm/encdec), or
# F = A + Lm*Bm + n_app*Ba for the hybrid (mamba layers + shared-block
# uses, from depths with no use, one use, and one use and one more layer).
# A is the fixed cost (embed, logits, loss, optimizer), B the per-layer
# cost; every reported quantity (flops, bytes, collective bytes) is
# extrapolated with the same coefficients.  Peak memory comes from
# the full-size scanned dry-run compile (no extrapolation).
import argparse
import json
from typing import Any, Dict, Optional

from repro.configs.base import SHAPES
from repro.configs.registry import cells, get_config, lm_archs
from repro.launch.dryrun import run_cell

PEAK_FLOPS = 197e12     # bf16 / chip
HBM_BW = 819e9          # B/s / chip
ICI_BW = 50e9           # B/s / link
CHIPS = 256             # single-pod roofline

METRICS = ("flops", "bytes_accessed", "fused_bytes", "collective_bytes")

ROOFLINE_PATH = "experiments/roofline_results.json"


def _pd(rec: Dict) -> Dict[str, float]:
    return {m: float(rec["per_device"][m]) for m in METRICS}


def _lin2(f1: Dict, f2: Dict) -> Dict[str, Dict[str, float]]:
    """F(L) = A + L*B from L=1,2 samples."""
    B = {m: f2[m] - f1[m] for m in METRICS}
    A = {m: f1[m] - B[m] for m in METRICS}
    return {"A": A, "B": B}


def hybrid_depths(cfg) -> tuple:
    """Depths whose unrolled costs separate a hybrid's terms: the Mamba
    layers before the first shared-block use (no use), one more (the first
    use and its layer) and two more (one more plain layer)."""
    first = cfg.hybrid_layer_ids[0]
    assert first + 1 not in cfg.hybrid_layer_ids, cfg.hybrid_layer_ids
    return first, first + 1, first + 2


def hybrid_terms(f0: Dict, f1: Dict, f2: Dict, cfg) -> Dict[str, Any]:
    """F = A + L*Bm + n_app*Ba from the costs at ``hybrid_depths``."""
    first = cfg.hybrid_layer_ids[0]
    Bm = {m: f2[m] - f1[m] for m in METRICS}
    Ba = {m: f1[m] - f0[m] - Bm[m] for m in METRICS}
    A = {m: f0[m] - first * Bm[m] for m in METRICS}
    n_app = len(cfg.hybrid_uses)
    total = {m: A[m] + cfg.n_layers * Bm[m] + n_app * Ba[m] for m in METRICS}
    return {"total": total, "fixed": A, "per_layer": Bm, "per_attn_app": Ba}


def extrapolate(arch: str, shape_name: str, *, attn_impl: str) -> Dict[str, Any]:
    """Per-device totals for the full layer count, via unrolled variants.

    ``accum_steps=1``: the microbatch loop is a lax.scan whose body the HLO
    cost analysis would count once; with no accumulation the totals cover
    the full global batch directly."""
    cfg = get_config(arch)
    kw = dict(attn_impl=attn_impl, scan_layers=False, multi_pod=False,
              accum_steps=1)

    if cfg.family == "hybrid":
        depths = hybrid_depths(cfg)
        fs = [_pd(run_cell(arch, shape_name, n_layers=n, **kw)) for n in depths]
        return {**hybrid_terms(*fs, cfg),
                "samples": {f"L{n}": f for n, f in zip(depths, fs)}}

    f1 = _pd(run_cell(arch, shape_name, n_layers=1, **kw))
    f2 = _pd(run_cell(arch, shape_name, n_layers=2, **kw))
    co = _lin2(f1, f2)
    L = cfg.n_layers
    total = {m: co["A"][m] + L * co["B"][m] for m in METRICS}
    return {
        "total": total,
        "fixed": co["A"],
        "per_layer": co["B"],
        "samples": {"L1": f1, "L2": f2},
    }


def model_flops_per_device(arch: str, shape_name: str) -> Dict[str, float]:
    """Useful-work floor: 6*N_active*D (train) / 2*N_active*D (inference)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.n_active_params()
    tokens = (
        shape.global_batch
        if shape.is_decode
        else shape.global_batch * shape.seq_len
    )
    mult = 6 if shape.mode == "train" else 2
    return {
        "n_active_params": n,
        "tokens_per_step": tokens,
        "model_flops_per_device": mult * n * tokens / CHIPS,
    }


def roofline_cell(
    arch: str,
    shape_name: str,
    *,
    attn_impl: str = "chunked",
    dryrun_record: Optional[Dict] = None,
) -> Dict[str, Any]:
    ext = extrapolate(arch, shape_name, attn_impl=attn_impl)
    tot = ext["total"]
    compute_s = tot["flops"] / PEAK_FLOPS
    # memory term uses the fusion-aware HBM-traffic estimate; the raw
    # unfused `bytes accessed` is kept as an upper bound
    memory_s = tot["fused_bytes"] / HBM_BW
    collective_s = tot["collective_bytes"] / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(arch, shape_name)
    bound = max(terms.values())
    rec = {
        "arch": arch,
        "shape": shape_name,
        "attn_impl": attn_impl,
        "mesh": "16x16",
        **terms,
        "memory_s_unfused_bound": tot["bytes_accessed"] / HBM_BW,
        "dominant": dominant,
        "useful_flops_ratio": (
            mf["model_flops_per_device"] / tot["flops"] if tot["flops"] else 0.0
        ),
        "roofline_fraction": (
            # fraction of the chip's peak the dominant resource implies for
            # useful model flops: (model_flops/peak) / step_time_bound
            (mf["model_flops_per_device"] / PEAK_FLOPS) / bound if bound else 0.0
        ),
        "totals_per_device": tot,
        "model_flops": mf,
        "extrapolation": ext,
    }
    if dryrun_record is not None and dryrun_record.get("status") == "ok":
        rec["peak_bytes_full_compile"] = dryrun_record["per_device"]["peak_bytes"]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--attn", default="chunked")
    ap.add_argument("--out", default=ROOFLINE_PATH)
    ap.add_argument("--dryrun-results", default="experiments/dryrun_results.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    try:
        with open(args.dryrun_results) as f:
            dres = json.load(f)
    except (OSError, json.JSONDecodeError):
        dres = {}
    try:
        with open(args.out) as f:
            results = json.load(f)
    except (OSError, json.JSONDecodeError):
        results = {}

    archs = lm_archs() if args.arch == "all" else [args.arch.replace("-", "_")]
    for arch in archs:
        shape_names = list(cells(arch)) if args.shape == "all" else [args.shape]
        for shape_name in shape_names:
            key = f"{arch}|{shape_name}|{args.attn}"
            if key in results and not args.force:
                print(f"[skip] {key}")
                continue
            print(f"[roofline] {key}", flush=True)
            dr = dres.get(f"{arch}|{shape_name}|16x16|{args.attn}")
            try:
                rec = roofline_cell(
                    arch, shape_name, attn_impl=args.attn, dryrun_record=dr
                )
                print(
                    f"  compute={rec['compute_s']*1e3:.2f}ms "
                    f"memory={rec['memory_s']*1e3:.2f}ms "
                    f"collective={rec['collective_s']*1e3:.2f}ms "
                    f"dominant={rec['dominant']} "
                    f"useful={rec['useful_flops_ratio']:.2f} "
                    f"roofline_frac={rec['roofline_fraction']:.3f}"
                )
            except Exception as e:  # noqa: BLE001
                rec = {"arch": arch, "shape": shape_name, "status": "error",
                       "error": f"{type(e).__name__}: {e}"}
                print(f"  FAILED: {rec['error']}")
            results[key] = rec
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
