"""Engine phases and paged-step scopes from one traced run of a cell.

    python3 chipbench/phases.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``chipbench/run.py --trace 1`` does and reads its profiler
trace a second time, for what ``trace_reduce`` does not keep: the serving
engine's ``serve.*`` phase spans (``repro.serve.engine``) and the
``jax.named_scope`` path of each device op (``repro.serve.paged_runner``).
Prints the run's result object, then one JSON object for the window:

* ``host_spans``: per span name, the benchmark's and the engine's, the host
  time of its spans (``ns``), their count (``n``), the device idle time
  inside them (``idle_ns``) and the part of it that no inner span holds
  (``self_idle_ns``);
* ``scopes``: device time per ``<program>/<scope>``;
* ``idle_gaps``: the ten longest device idle gaps, each named by the
  innermost span that holds it;
* ``per_step_ms``: the same host and device times per window step, and
  ``phase_share_of_step_idle``, the share of the device idle inside the
  harness's step spans that an engine phase below ``serve.step`` holds.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple
from unittest import mock

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from chipbench import trace_reduce as tr  # noqa: E402

Span = Tuple[float, float, str]

#: prefix of the serving engine's phase spans
PHASE_PREFIX = "serve."
#: the engine span around a whole ``ServeEngine.step()``
ENGINE_STEP = "serve.step"
#: stat of an ``XLA Ops`` event's metadata that holds its scope path, e.g.
#: ``jit(paged_decode_step)/paged_lse/reduce_max:`` (read from a v5e trace);
#: ``ProfileData`` gives an event's own stats only, so ``op_paths`` reads it
SCOPE_STAT = "tf_op"


def scope_of(path: str) -> str | None:
    """``jit(paged_decode_step)/paged_lse/jit(_where)/select_n`` ->
    ``paged_lse``: the outermost named scope of an op's scope path, or None
    for an op traced under no scope."""
    parts = [p for p in path.split(";", 1)[0].split("/") if p]
    while parts and parts[0].startswith("jit("):
        parts.pop(0)
    return parts[0] if len(parts) >= 2 else None


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of the protobuf message in ``buf[lo:hi]``; a
    length-delimited value comes as its (start, end) in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def op_paths(path: str | Path) -> List[Dict[Tuple[int, str], str]]:
    """For each device plane, in the trace's order, the ``SCOPE_STAT`` of
    each op's event metadata, keyed by (program id, op event name).  Reads
    the ``XSpace`` protobuf: planes 1; a plane's name 2, event metadata 4
    and stat metadata 5, both maps of key 1 to value 2; an event metadata's
    name 2 and stats 5; a stat's metadata id 1, integers 3 and 4, string 5
    and interned string 7."""
    buf = Path(path).read_bytes()
    text = lambda v: buf[v[0]:v[1]].decode("utf-8", "replace")  # noqa: E731
    out: List[Dict[Tuple[int, str], str]] = []
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = text(v)
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                for ef, ev in _fields(buf, *v):
                    if ef == 2:
                        sm = dict(_fields(buf, *ev))
                        stat_names[sm.get(1, 0)] = text(sm[2]) if 2 in sm else ""
        if not tr.DEVICE_PLANE.match(name):
            continue
        paths: Dict[Tuple[int, str], str] = {}
        for entry in metas:
            for ef, ev in _fields(buf, *entry):
                if ef != 2:
                    continue
                op, prog, scope = "", 0, None
                for mf, mv in _fields(buf, *ev):
                    if mf == 2:
                        op = text(mv)
                    elif mf == 5:
                        st = dict(_fields(buf, *mv))
                        stat = stat_names.get(st.get(1))
                        if stat == "program_id":
                            prog = st.get(3, st.get(4, 0))
                        elif stat == SCOPE_STAT:
                            scope = text(st[5]) if 5 in st else stat_names.get(st.get(7))
                if scope:
                    paths[(prog, op)] = scope
        out.append(paths)
    return out


def program_id(module_event: str) -> int:
    """``jit_scatter(1234)`` -> 1234."""
    return int(module_event.rsplit("(", 1)[1].rstrip(")"))


def load(path: str | Path) -> Dict:
    """Events of one trace as ``trace_reduce.load`` gives them, with the
    engine's spans among ``spans``, the arguments of every span in
    ``span_args`` (in the order of ``spans``) and, per device plane,
    ``scopes``: the ops traced under a named scope, as
    (start_ns, end_ns, ``<program>/<scope>``)."""
    from jax.profiler import ProfileData

    events = tr.load(path)
    for dev, paths in zip(events["devices"], op_paths(path)):
        mods = sorted(dev["modules"])
        starts = [m[0] for m in mods]
        dev["scopes"] = []
        for s, e, name in dev["ops"]:
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or mods[k][1] < s:
                continue
            scope = scope_of(paths.get((program_id(mods[k][2]), name), ""))
            if scope:
                dev["scopes"].append((s, e, f"{tr.module_name(mods[k][2])}/{scope}"))
    spans = [(s, e, n, {}) for s, e, n in events["spans"]]
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                             for e in line.events if e.name.startswith(PHASE_PREFIX))
    spans.sort(key=lambda x: x[:3])
    events["spans"] = [x[:3] for x in spans]
    events["span_args"] = [x[3] for x in spans]
    return events


def parents(spans: List[Span]) -> List[int]:
    """For each span of ``spans``, sorted by start and then longest first,
    the index of the innermost other span that holds it, or -1."""
    out: List[int] = []
    stack: List[int] = []
    for i, (_, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] < e:
            stack.pop()
        out.append(stack[-1] if stack else -1)
        stack.append(i)
    return out


def reduce(events: Dict, window_span: str, step_span: str) -> Dict:
    """Host spans and scopes of the first ``window_span``, with the idle
    gaps that ``trace_reduce.reduce`` names from every span of ``events``;
    device times are averaged over the device planes."""
    base = tr.reduce(events, window_span, step_span)
    w0, w1 = next((s, e) for s, e, n in events["spans"] if n == window_span)
    in_window = sorted(((s, e, n) for s, e, n in events["spans"]
                        if n != window_span and s >= w0 and e <= w1),
                       key=lambda x: (x[0], -x[1]))
    up = parents(in_window)
    ndev = len(events["devices"])
    host: Dict[str, Dict[str, float]] = {}
    for s, e, name in in_window:
        h = host.setdefault(name, {"ns": 0.0, "n": 0, "idle_ns": 0.0, "self_idle_ns": 0.0})
        h["ns"] += e - s
        h["n"] += 1
    scopes: Dict[str, float] = defaultdict(float)
    for dev in events["devices"]:
        busy = tr.union([(s, e) for s, e, _ in dev["ops"]])
        for (s, e, name), i in zip(in_window, up):
            idle = ((e - s) - tr.covered(busy, s, e)) / ndev
            host[name]["idle_ns"] += idle
            host[name]["self_idle_ns"] += idle
            if i >= 0:
                host[in_window[i][2]]["self_idle_ns"] -= idle
        for s, e, key in dev.get("scopes", []):
            if s >= w0 and e <= w1:
                scopes[key] += (e - s) / ndev
    steps, step_idle = base["steps"], base["idle_in_steps_ns"]
    phase_idle = sum(h["self_idle_ns"] for name, h in host.items()
                     if name.startswith(PHASE_PREFIX) and name != ENGINE_STEP)
    per = (lambda ns: ns / steps / 1e6) if steps else (lambda ns: None)
    return {
        "window_ns": base["window_ns"],
        "steps": steps,
        "host_spans": host,
        "scopes": dict(scopes),
        "idle_gaps": base["breakdown"]["idle_gaps"],
        "per_step_ms": {
            "host": {name: per(h["ns"]) for name, h in host.items()},
            "idle": {name: per(h["idle_ns"]) for name, h in host.items()},
            "self_idle": {name: per(h["self_idle_ns"]) for name, h in host.items()},
            "scopes": {name: per(ns) for name, ns in scopes.items()},
        },
        "phase_share_of_step_idle": phase_idle / step_idle if step_idle else None,
    }


def xplane_of(trace_dir: str) -> str:
    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(files)}")
    return files[0]


def main(argv=None) -> None:
    from chipbench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    found: Dict = {}
    reduce_dir = tr.reduce_dir

    def reduce_both(trace_dir, window_span, step_span):
        found.update(reduce(load(xplane_of(trace_dir)), window_span, step_span))
        return reduce_dir(trace_dir, window_span, step_span)

    with mock.patch.object(tr, "reduce_dir", reduce_both):
        result = run.run_cell(Path.cwd(), args.workload, args.seed, args.seconds, True)
    print(json.dumps(result), flush=True)
    print(json.dumps(found), flush=True)


if __name__ == "__main__":
    main()
