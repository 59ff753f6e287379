"""BENCHMARK.json against the contract it is written to, and every file it
names present."""
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "chipbench/run.py"] and B["paths"] == ["chipbench"]
    assert 1 <= B["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in B[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert {m["name"] for m in B["end_to_end"]} >= {"setup_s"}
    assert all(0.01 <= m["bound"] <= 0.25 for m in B["end_to_end"])


def test_every_named_file_exists():
    for c in B["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["reduced"] == cfg["reduced"]
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    for w in B["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "chipbench" / "limits" / f"{w['name']}.json").exists()
    for m in B["end_to_end"] + B["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").exists()
    e2e = {m["name"] for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
