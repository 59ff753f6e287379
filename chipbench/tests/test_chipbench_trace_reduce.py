"""The reduction from profiler trace to metrics, on hand-made events and on
a small trace recorded on a TPU v5e."""
import gzip
import json
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "chatglm3_window.json.gz"


def test_hand_made_events():
    events = {
        "devices": [{
            "ops": [(0, 10, "%a.1 = f32[] add()"), (5, 15, "%paged_attention.3 = bf16[] custom-call()"),
                    (30, 40, "%copy.2 = bf16[] copy()")],
            "modules": [(0, 16, "jit_paged_decode_step(1)"), (30, 41, "jit_scatter(2)")],
        }],
        "spans": [(0, 100, "chipbench.window"), (0, 20, "chipbench.step"),
                  (20, 25, "chipbench.client"), (25, 45, "chipbench.step")],
    }
    r = tr.reduce(events, "chipbench.window", "chipbench.step")
    assert (r["window_ns"], r["busy_ns"], r["steps"], r["idle_in_steps_ns"]) == (100, 25, 2, 15)
    assert r["programs"]["jit_paged_decode_step"] == {"ns": 16, "n": 1}
    assert r["programs"]["jit_scatter"] == {"ns": 11, "n": 1}
    assert r["ops"] == {"a": 10, "paged_attention": 10, "copy": 10}
    # idle gaps: [15, 30] falls in the client span, [40, 100] in no span
    assert r["breakdown"]["idle_gaps"] == [["outside spans", 60e-9], ["chipbench.client", 15e-9]]
    assert r["breakdown"]["device_ops"][0][0].startswith("jit_")


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"devices": [], "spans": []}, "chipbench.window", "chipbench.step")


def test_recorded_v5e_trace():
    with gzip.open(FIXTURE, "rt") as fh:
        events = json.load(fh)
    r = tr.reduce(events, "chipbench.window", "chipbench.step")
    assert 0 < r["busy_ns"] <= r["window_ns"] and r["steps"] >= 1
    # one paged decode step per engine step, each with the kernel inside it
    assert r["programs"]["jit_paged_decode_step"]["n"] == r["steps"]
    assert r["ops"]["paged_attention"] > 0
    assert r["programs"]["jit_scatter"]["ns"] > 0
    assert 0 <= r["idle_in_steps_ns"] <= r["window_ns"] - r["busy_ns"] + 1
    assert len(r["breakdown"]["device_ops"]) == 10
