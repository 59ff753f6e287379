"""A checkout-shaped tree with a tiny configuration, for CPU runs of the
harness: BENCHMARK.json, the benchmark's directory with a config, a mix,
limits, the real metric readers and family modules, and the program's
``src``."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny-cell"

#: widths of the tiny configuration (the program's and the reference's names)
TINY = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
        "d_ff": 256, "vocab_size": 512}


def build(tmp: Path, limit: float, extra_metric: str | None = None) -> Path:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    d = tmp / "bench"
    for sub in ("configs", "traffic", "limits"):
        (d / sub).mkdir(parents=True)
    for sub in ("metrics", "families"):
        shutil.copytree(ROOT / "chipbench" / sub, d / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "src").symlink_to(ROOT / "src")
    cfg = json.loads((ROOT / "chipbench" / "configs" / "stablelm_1_6b.json").read_text())
    cfg.update({"num_hidden_layers": TINY["n_layers"], "hidden_size": TINY["d_model"],
                "num_attention_heads": TINY["n_heads"], "num_key_value_heads": TINY["n_kv_heads"],
                "intermediate_size": TINY["d_ff"], "vocab_size": TINY["vocab_size"],
                "overrides": TINY})
    cfg["reference"].update({"head_dim": TINY["head_dim"], "rotary_dims": TINY["head_dim"]})
    (d / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (d / "traffic" / "tinymix.json").write_text(json.dumps({
        "loop": "closed", "sessions": 4, "prompt_tokens": {"values": [8, 24], "weights": [1, 1]},
        "answer_tokens": {"values": [12, 20], "weights": [1, 1]},
        "block_size": 16, "blocks_per_arena": 4}))
    (d / "limits" / f"{CELL}.json").write_text(json.dumps(
        {"logit_gap_max": limit, "tokens_compared_min": 16, "check_requests": 64}))
    bench["paths"] = ["bench"]
    bench["configs"] = [{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "tinymix", "chips": 1,
                           "why": "test"}]
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    if extra_metric:
        (d / "metrics" / f"{extra_metric}.py").write_text(
            "def read(run):\n"
            "    w = run['window']\n"
            "    return sum(1 for t in run['token_times'].values()\n"
            "               if any(w['t0'] <= x <= w['t1'] for x in t))\n")
        bench["end_to_end"].append({"name": extra_metric, "unit": "requests", "better": "higher",
                                    "bound": 0.25, "source": "host_clock"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
