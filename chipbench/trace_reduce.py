"""Profiler trace -> device busy and idle, per-program and per-kernel time,
and idle gaps by the harness's host span.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX.  On a TPU the device planes are ``/device:TPU:<n>``; their
``XLA Modules`` line holds one event per program run (``jit_<name>(<id>)``)
and their ``XLA Ops`` line one event per operation (``%<op>.<k> = ...``;
a Pallas kernel's op carries the kernel's name).  Host spans are the
benchmark's own ``jax.profiler.TraceAnnotation`` events, named
``chipbench.*``, on the host plane; host and device events share one
clock in the trace.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "chipbench."


def module_name(event_name: str) -> str:
    """``jit_scatter(1234)`` -> ``jit_scatter``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%paged_attention.7 = bf16[...] custom-call(...)`` -> ``paged_attention``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def load(path: str | Path) -> Dict:
    """Events of one trace: per device plane its ops and modules, and the
    benchmark's host spans, as (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": sorted(spans)}


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def covered(busy: List[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the sorted disjoint ``busy`` covers."""
    i = max(bisect.bisect_right(busy, (lo, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(busy) and busy[i][0] < hi:
        s, e = busy[i]
        total += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return total


def reduce(events: Dict, window_span: str, step_span: str) -> Dict:
    """Reduce the events to what the metric readers take.

    Window quantities cover the first ``window_span`` host span; ``*_all``
    quantities cover the whole trace.  Device times are averaged over the
    device planes."""
    spans = events["spans"]
    windows = [(s, e) for s, e, n in spans if n == window_span]
    if not windows or not events["devices"]:
        raise ValueError(f"trace has no {window_span!r} span or no TPU device plane")
    w0, w1 = windows[0]
    steps = [(s, e) for s, e, n in spans if n == step_span and s >= w0 and e <= w1]
    inner = [(s, e, n) for s, e, n in spans if n != window_span]
    ndev = len(events["devices"])
    busy_ns = idle_in_steps = 0.0
    programs: Dict[str, Dict[str, float]] = defaultdict(lambda: {"ns": 0.0, "n": 0})
    programs_all: Dict[str, Dict[str, float]] = defaultdict(lambda: {"ns": 0.0, "n": 0})
    ops: Dict[str, float] = defaultdict(float)
    by_module_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    for dev in events["devices"]:
        busy = union([(s, e) for s, e, _ in dev["ops"]])
        win_busy = clip(busy, w0, w1)
        busy_ns += length(win_busy)
        idle_in_steps += sum((e - s) - covered(busy, s, e) for s, e in steps)
        mods = sorted(dev["modules"])
        starts = [m[0] for m in mods]
        for s, e, name in mods:
            prog = module_name(name)
            programs_all[prog]["ns"] += (e - s) / ndev
            programs_all[prog]["n"] += 1
            if s >= w0 and e <= w1:
                programs[prog]["ns"] += (e - s) / ndev
                programs[prog]["n"] += 1
        for s, e, name in dev["ops"]:
            if s < w0 or e > w1:
                continue
            k = bisect.bisect_right(starts, s) - 1
            prog = module_name(mods[k][2]) if k >= 0 and mods[k][1] >= s else "?"
            ops[op_name(name)] += (e - s) / ndev
            by_module_op[f"{prog}/{op_name(name)}"] += (e - s) / ndev
        prev = w0
        for s, e in win_busy + [(w1, w1)]:
            if s > prev:
                gaps.append((s - prev, host_span_at(inner, (prev + s) / 2)))
            prev = max(prev, e)
    gaps.sort(reverse=True)
    top_ops = sorted(by_module_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_ns": w1 - w0,
        "busy_ns": busy_ns / ndev,
        "steps": len(steps),
        "idle_in_steps_ns": idle_in_steps / ndev,
        "programs": dict(programs),
        "programs_all": dict(programs_all),
        "ops": dict(ops),
        "breakdown": {
            "device_ops": [[name, ns / 1e9] for name, ns in top_ops],
            "idle_gaps": [[name, ns / 1e9 / ndev] for ns, name in gaps[:10]],
        },
    }


def host_span_at(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The innermost benchmark span that holds time ``t``."""
    best = None
    for s, e, n in spans:
        if s > t:
            break
        if e >= t and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "outside spans"


def reduce_dir(trace_dir: str, window_span: str, step_span: str) -> Dict:
    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(files)}")
    return reduce(load(files[0]), window_span, step_span)
