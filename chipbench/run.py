"""On-chip serving benchmark: one cell of ``BENCHMARK.json`` per run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  The cell names a configuration file (``chipbench/configs/``) and a
traffic mix (``chipbench/traffic/``); metrics are readers in
``chipbench/metrics/<name>.py``, limits of the correctness check are in
``chipbench/limits/<cell>.json``.  Nothing here knows a cell, a
configuration, a mix or a metric by name.

Set-up makes the weights from the seed on the device, builds the
program's ``ServeEngine`` over a pool sized by the mix, fills the batch
with every session's first turn (which holds every prompt length of the
mix, so every prefill shape compiles or loads here) and runs two decode
steps.  The window then drives the closed session loop for ``--seconds``
and ends on a step boundary.  Afterwards the engine is freed and the
reference checks a seeded sample of the served requests.  The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error and the last key of that
object.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from chipbench import counts, reference, trace_reduce, traffic, weights, window  # noqa: E402

#: JAX monitoring events that mean a program was compiled or loaded
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return cell, configs[cell["config"]]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(bench_dir: Path, name: str):
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(n: int):
    """The first device, which must be a TPU, with at least ``n`` of them."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise SystemExit(f"chipbench: needs {n} TPU chip(s), JAX found "
                         f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs[0]


def program_config(cfg_file: dict, spec: dict):
    """The program's ModelConfig for this configuration file, checked
    against the reference's sizes ``spec``."""
    from repro.configs.registry import get_config

    pcfg = dataclasses.replace(get_config(cfg_file["arch"]), **cfg_file["overrides"])
    have = {"layers": pcfg.n_layers, "d_model": pcfg.d_model, "heads": pcfg.n_heads,
            "kv_heads": pcfg.n_kv_heads, "head_dim": pcfg.hd, "d_ff": pcfg.d_ff,
            "vocab": pcfg.vocab_size, "norm": pcfg.norm, "compute_dtype": pcfg.dtype,
            "kv_dtype": pcfg.kv_cache_dtype, "rope_theta": pcfg.rope_theta,
            "rotary_dims": pcfg.hd // 2 if pcfg.rope == "rope2d" else pcfg.hd}
    bad = {k: (v, spec.get(k)) for k, v in have.items() if spec.get(k) != v}
    if bad or pcfg.activation != "swiglu" or pcfg.family != "dense":
        raise SystemExit(f"chipbench: program config departs from the file: {bad}")
    return pcfg


class Loop:
    """The closed session loop over a ``ServeEngine``: submits turns, stamps
    every token with the host time its step returned, resubmits a session's
    next turn as soon as its last one is done."""

    def __init__(self, eng, sessions: traffic.Sessions, request_cls):
        self.eng, self.sessions, self.Request = eng, sessions, request_cls
        self.records: dict[int, dict] = {}
        self.active: list[int] = []
        self._rid = 0

    def submit(self, session: int) -> None:
        from repro.robustness import RequestRejected

        prompt, max_new = self.sessions.next_turn(session)
        req = self.Request(rid=self._rid, prompt=prompt, max_new=max_new)
        self.records[self._rid] = {"session": session, "req": req, "times": []}
        self.active.append(self._rid)
        self._rid += 1
        try:
            self.eng.submit(req)
        except RequestRejected:
            pass                                   # harvested as failed

    def harvest(self, t: float) -> dict:
        """Stamp the tokens of the step that returned at ``t``; resubmit
        finished sessions.  Returns the step's work for the counts."""
        past_lens, prefills, ended = [], [], []
        for rid in self.active:
            rec = self.records[rid]
            req = rec["req"]
            new = len(req.out) - len(rec["times"])
            if new > 0:
                if not rec["times"]:
                    prefills.append(len(req.prompt))
                rec["times"].extend([t] * new)
                past_lens.append(len(req.prompt) + len(req.out) - 2)
            if req.status in ("done", "rejected", "cancelled"):
                ended.append(rid)
        for rid in ended:
            self.active.remove(rid)
            self.submit(self.records[rid]["session"])
        return {"past_lens": past_lens, "prefills": prefills}


def span(trace: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if trace else nullcontext()


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             *, require_chip: bool = True, control: bool = False,
             t_start: float = T_START) -> dict:
    """One run of ``workload``; returns the result object.  ``control``
    puts the fp8 control in the program's place for the check
    (``control.py``): the comparison scores the tokens the control puts
    first, so ``correct`` is the control's, and ``readings`` keeps the
    program's widest gap beside it.  ``t_start`` is when set-up began (the
    process start for ``run.py``)."""
    bench = load_benchmark(root)
    bench_dir = root / bench["paths"][0]
    cell, cfg_entry = find_cell(bench, workload)
    cfg_file = json.loads((root / cfg_entry["file"]).read_text())
    mix = traffic.load_mix(bench_dir / "traffic" / f"{cell['traffic']}.json")
    limits = json.loads((bench_dir / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if applies(m, workload)]
    layer = [m for m in bench["per_layer"] if applies(m, workload)]
    readers = {m["name"]: load_reader(bench_dir, m["name"]) for m in (layer if trace else e2e)}

    dev = require_chips(cell["chips"]) if require_chip else jax.devices()[0]
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    from repro.core.kv_pool import KVPoolConfig
    from repro.launch.serve import use_compile_cache
    from repro.models.transformer import LM
    from repro.serve.engine import Request, ServeEngine

    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, d, **_: compiles.append(time.perf_counter()) if e in COMPILE_EVENTS else None)
    jax.monitoring.register_event_listener(
        lambda e, **_: compiles.append(time.perf_counter()) if e in COMPILE_EVENTS else None)
    log(f"device platform={dev.platform} kind={dev.device_kind} count={len(jax.devices())} "
        f"compile_cache={cache_dir}")

    phases = {"start": time.time() - t_start}
    spec = reference.spec_of(cfg_file)
    pcfg = program_config(cfg_file, spec)
    model = LM(pcfg, attn_impl="naive", remat=None)
    padded_vocab = jax.eval_shape(model.init, jax.random.key(0))["embed"]["tok"].shape[0]
    w = jax.block_until_ready(
        weights.make_weights(spec, padded_vocab, traffic.seed_state(seed, 1)))
    params = weights.program_params(w, model)
    phases["weights"] = time.time() - t_start
    num_blocks, per_seq = traffic.pool_sizing(mix)
    pool_cfg = KVPoolConfig(
        num_blocks=num_blocks, block_size=mix["block_size"], kv_heads=pcfg.n_kv_heads,
        head_dim=pcfg.hd, n_layers=pcfg.n_layers, max_seqs=mix["sessions"],
        max_blocks_per_seq=per_seq, blocks_per_arena=mix["blocks_per_arena"],
        dtype=pcfg.kv_cache_dtype)
    eng = ServeEngine(model, params, pool_cfg)
    log(f"model={pcfg.name} layers={pcfg.n_layers} pool pages={num_blocks}x{mix['block_size']} "
        f"max_pages_per_seq={per_seq} bytes_per_pool={eng.pool.k.nbytes} "
        f"sessions={mix['sessions']} kernels={eng.use_kernel}")

    phases["engine"] = time.time() - t_start
    loop = Loop(eng, traffic.Sessions(mix, seed, spec["vocab"]), Request)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    # fill: every session's first turn, admitted, prefilled and decoded twice
    fill_log = []
    with span(trace, "chipbench.fill"):
        for s in range(mix["sessions"]):
            loop.submit(s)
        for _ in range(2):
            eng.step()
            fill_log.append(loop.harvest(time.perf_counter()))
        jax.block_until_ready((eng.pool.k, eng.pool.v))
    n_compiles_setup = len(compiles)
    phases["fill"] = time.time() - t_start
    log("set-up seconds since start, by phase end: "
        + " ".join(f"{k}={v:.3f}" for k, v in phases.items()))

    steps_log, step_ends = [], []
    t0 = time.perf_counter()
    setup_s = time.time() - t_start
    attempted = set(loop.active)
    with span(trace, "chipbench.window"):
        while True:
            with span(trace, "chipbench.step"):
                eng.step()
            t = time.perf_counter()
            step_ends.append(t)
            with span(trace, "chipbench.client"):
                steps_log.append(loop.harvest(t))
                attempted.update(loop.active)
            if window.closes(t, t0, seconds):
                break
        with span(trace, "chipbench.drain"):
            jax.block_until_ready((eng.pool.k, eng.pool.v))
    t1 = time.perf_counter()
    compiles_in_window = sum(1 for c in compiles if t0 <= c <= t1)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce_dir(trace_dir, window_span="chipbench.window",
                                          step_span="chipbench.step")
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    m = eng.metrics()
    log(f"setup_s={setup_s!r} compiles_in_setup={n_compiles_setup} "
        f"compiles_in_window={compiles_in_window} window_s={t1 - t0!r} steps={len(steps_log)} "
        f"preemptions={int(m['preemptions'])} rejected={int(m['rejected'])} "
        f"cancelled={int(m['cancelled'])} done={int(m['done'])} peak_bytes_in_use={peak}")
    durs = np.diff([t0] + step_ends)
    log(f"step_s median={float(np.median(durs))!r} slowest="
        + " ".join(f"#{i}:{durs[i]:.3f}" for i in np.argsort(-durs)[:5]))
    failed_in_window = sum(1 for rid in attempted
                           if loop.records[rid]["req"].status in ("rejected", "cancelled"))

    # the program's state goes before the reference runs; the weights stay
    del eng, params
    gc.collect()
    token_times = {rid: rec["times"] for rid, rec in loop.records.items()}
    run = {
        "workload": workload, "seed": seed, "spec": spec, "mix": mix,
        "setup_s": setup_s, "window": {"t0": t0, "t1": t1, "seconds": seconds},
        "token_times": token_times, "steps": steps_log, "fill": fill_log,
        "compiles_in_window": compiles_in_window, "peak_bytes": peak,
        "preemptions": int(m["preemptions"]), "trace": reduced,
        "peaks": counts.peaks(dev.device_kind) if require_chip else None,
    }
    metrics = {}
    for entry in (layer if trace else e2e):
        value = readers[entry["name"]](run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    t_check = time.perf_counter()
    checks, readings = check_served(w, spec, mix, loop, t0, t1, seed, limits, control)
    log(f"reference check over {readings['tokens']} served tokens took "
        f"{time.perf_counter() - t_check:.3f} s")
    correct = all(c["ok"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(attempted), "failed": failed_in_window,
              "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_ns"] / 1e9
        device["window_s"] = reduced["window_ns"] / 1e9
        result["breakdown"] = reduced["breakdown"]
    if control:
        result["readings"] = readings
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k} = {c['value']!r} {c['rule']} limit {c['limit']!r}: "
            f"{'ok' if c['ok'] else 'FAILED'}")
    return result


def check_served(w, spec, mix, loop: Loop, t0: float, t1: float, seed: int,
                 limits: dict, control: bool = False) -> tuple[dict, dict]:
    """Reference check of a seeded sample of the requests that served
    tokens in the window, the one with the most served tokens among them.
    The checks compare the program's served tokens or, with ``control``,
    the tokens the fp8 control puts first at the same positions; the
    readings hold both sides' widest gaps."""
    cands = sorted(rid for rid, rec in loop.records.items()
                   if any(t0 <= t <= t1 for t in rec["times"]))
    gap_max, ctrl_max, compared = 0.0, 0.0, 0
    if cands:
        longest = max(cands, key=lambda r: (len(loop.records[r]["req"].out), -r))
        rest = [r for r in cands if r != longest]
        rng = np.random.default_rng(traffic.seed_state(seed, 3))
        k = min(limits["check_requests"] - 1, len(rest))
        sample = [longest] + sorted(rng.choice(rest, size=k, replace=False).tolist())
        span_ = max(mix["answer_tokens"]["values"])
        bucket = -(-(max(mix["prompt_tokens"]["values"]) + span_) // 128) * 128
        for rid in sample:
            req = loop.records[rid]["req"]
            gaps, ctrl = reference.served_gaps(w, spec, req.prompt, req.out, span_, bucket,
                                               control=control)
            gap_max = max(gap_max, float(gaps.max()))
            if control:
                ctrl_max = max(ctrl_max, float(ctrl.max()))
            compared += len(gaps)
    readings = {"program_gap_max": gap_max, "control_gap_max": ctrl_max, "tokens": compared}
    checked = ctrl_max if control else gap_max
    return {
        "logit_gap_max": {"value": checked, "limit": limits["logit_gap_max"], "rule": "<=",
                          "ok": bool(checked <= limits["logit_gap_max"])},
        "tokens_compared": {"value": compared, "limit": limits["tokens_compared_min"],
                            "rule": ">=", "ok": compared >= limits["tokens_compared_min"]},
    }, readings


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(Path.cwd(), args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
