"""A checkout-shaped tree with a tiny Zamba2 configuration, for CPU runs of
the harness with the ``zamba2`` family: the published configuration file
with its sizes cut, both shared blocks and three uses (blocks A, B, A)."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny-zamba2"

#: published keys at tiny widths, and the program's overrides to match
PUBLISHED = {"num_hidden_layers": 5, "hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 4, "attention_head_dim": 32, "intermediate_size": 96,
             "vocab_size": 256, "mamba_d_state": 16, "n_mamba_heads": 8, "mamba_headdim": 16,
             "adapter_rank": 8, "hybrid_layer_ids": [1, 2, 4]}
PROGRAM = {"n_layers": 5, "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 32,
           "d_ff": 96, "vocab_size": 256, "ssm_state": 16, "ssm_head_dim": 16,
           "adapter_rank": 8, "hybrid_layer_ids": [1, 2, 4]}


def build(tmp: Path, limit: float) -> Path:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    d = tmp / "bench"
    for sub in ("configs", "traffic", "limits"):
        (d / sub).mkdir(parents=True)
    for sub in ("metrics", "families"):
        shutil.copytree(ROOT / "chipbench" / sub, d / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "src").symlink_to(ROOT / "src")
    cfg = json.loads((ROOT / "chipbench" / "configs" / "zamba2_7b_18layers.json").read_text())
    cfg.update(PUBLISHED, overrides=PROGRAM)
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
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
