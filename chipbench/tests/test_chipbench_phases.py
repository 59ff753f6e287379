"""Engine phases and step scopes from a profiler trace: the reduction on
hand-made events and on the recorded v5e trace, and the serving engine's
spans as a CPU trace records them."""
import gzip
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import phases
from chipbench import trace_reduce as tr

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "chatglm3_window.json.gz"
PHASES = ("serve.admit", "serve.prefill", "serve.decode_dispatch", "serve.sample",
          "serve.kv_writeback", "serve.maintain")


@pytest.mark.parametrize("path, scope", [
    ("jit(paged_decode_step)/paged_lse/jit(_where)/select_n", "paged_lse"),
    ("jit(paged_decode_step)/attn_proj/bsd,dhk->bshk/dot_general", "attn_proj"),
    ("jit(paged_decode_step)/paged_attention/pallas_call", "paged_attention"),
    ("jit(paged_decode_step)/convert_element_type", None),
    ("jit(f)/broadcast_in_dim;jit(f)/squeeze", None),
    ("reduce_sum", None),
    ("", None),
])
def test_scope_of(path, scope):
    assert phases.scope_of(path) == scope


def test_hand_made_events():
    events = {
        "devices": [{
            "ops": [(30, 80, "%fusion.1 = bf16[] fusion()"),
                    (150, 170, "%paged_attention.2 = bf16[] custom-call()"),
                    (170, 190, "%fusion.4 = f32[] fusion()"),
                    (320, 340, "%copy.3 = bf16[] copy()")],
            "modules": [(30, 80, "jit_decode_step(1)"), (150, 190, "jit_paged_decode_step(2)"),
                        (320, 340, "jit_scatter(3)")],
            "scopes": [(150, 170, "jit_paged_decode_step/paged_attention"),
                       (170, 190, "jit_paged_decode_step/paged_lse"),
                       (1100, 1200, "jit_paged_decode_step/paged_lse")],
        }],
        "spans": [(0, 1000, "chipbench.window"), (0, 500, "chipbench.step"),
                  (5, 495, "serve.step"), (10, 100, "serve.admit"), (20, 90, "serve.prefill"),
                  (100, 150, "serve.decode_dispatch"), (150, 200, "serve.sample"),
                  (200, 300, "serve.kv_writeback"), (300, 400, "serve.kv_writeback"),
                  (500, 520, "chipbench.client")],
    }
    r = phases.reduce(events, "chipbench.window", "chipbench.step")
    h = r["host_spans"]
    assert h["serve.kv_writeback"] == {"ns": 200, "n": 2, "idle_ns": 180, "self_idle_ns": 180}
    assert h["serve.admit"] == {"ns": 90, "n": 1, "idle_ns": 40, "self_idle_ns": 20}
    assert h["serve.prefill"]["self_idle_ns"] == 20
    assert h["serve.step"]["idle_ns"] == 380 and h["serve.step"]["self_idle_ns"] == 100
    assert h["chipbench.step"]["idle_ns"] == 390 and h["chipbench.step"]["self_idle_ns"] == 10
    assert h["chipbench.client"]["idle_ns"] == 20
    # the self idle of every span in the step adds up to the step's idle
    inside = ("chipbench.step", "serve.step") + PHASES[:-1]
    assert sum(h[n]["self_idle_ns"] for n in inside) == h["chipbench.step"]["idle_ns"]
    assert r["phase_share_of_step_idle"] == pytest.approx(280 / 390)
    assert r["scopes"] == {"jit_paged_decode_step/paged_attention": 20,
                           "jit_paged_decode_step/paged_lse": 20}
    # each gap is named by the innermost span around its middle
    assert r["idle_gaps"] == [["outside spans", 660e-9], ["serve.kv_writeback", 130e-9],
                              ["serve.decode_dispatch", 70e-9], ["serve.admit", 30e-9]]
    assert r["steps"] == 1 and r["per_step_ms"]["host"]["serve.sample"] == 50e-6
    assert r["per_step_ms"]["scopes"]["jit_paged_decode_step/paged_lse"] == 20e-6


def test_recorded_v5e_trace_agrees_with_trace_reduce():
    with gzip.open(FIXTURE, "rt") as fh:
        events = json.load(fh)
    r = phases.reduce(events, "chipbench.window", "chipbench.step")
    base = tr.reduce(events, "chipbench.window", "chipbench.step")
    assert r["steps"] == base["steps"] and r["window_ns"] == base["window_ns"]
    assert r["host_spans"]["chipbench.step"]["idle_ns"] == pytest.approx(base["idle_in_steps_ns"])
    assert r["idle_gaps"] == base["breakdown"]["idle_gaps"]
    # a trace of a program without the engine's spans and scopes has none to read
    assert r["scopes"] == {} and r["phase_share_of_step_idle"] == 0
    assert not any(n.startswith(phases.PHASE_PREFIX) for n in r["host_spans"])


def _varint(x):
    out = b""
    while True:
        out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
        x >>= 7
        if not x:
            return out


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(data)) + data


def _stats(num, stats):
    return b"".join(_field(num, _field(1, sid) + _field(f, v)) for sid, f, v in stats)


def _plane(pid, name, lines, event_md, stat_md):
    """An XPlane: ``lines`` are (name, [(metadata id, start ns, duration ns,
    stats)]), ``event_md`` (id, name, stats) and ``stat_md`` {id: name}; a
    stat is (stat metadata id, value field, value)."""
    out = _field(1, pid) + _field(2, name)
    for line_name, events in lines:
        body = _field(2, line_name) + _field(3, 0)
        for mid, start, dur, ev_stats in events:
            ev = _field(1, mid) + _field(2, start * 1000) + _field(3, dur * 1000)
            body += _field(4, ev + _stats(4, ev_stats))
        out += _field(3, body)
    for mid, md_name, md_stats in event_md:
        md = _field(1, mid) + _field(2, md_name) + _stats(5, md_stats)
        out += _field(4, _field(1, mid) + _field(2, md))
    for sid, sname in stat_md.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid) + _field(2, sname)))
    return _field(1, out)


def test_load_reads_spans_and_the_scope_stat_of_event_metadata(tmp_path):
    # an XSpace as a v5e trace writes it: an op's scope path is a stat of
    # its event *metadata*, named through the plane's stat metadata
    fusion = "%fusion.3 = f32[] fusion()"
    tpu = _plane(1, "/device:TPU:0", [
        ("XLA Modules", [(1, 100, 50, []), (2, 200, 50, [])]),
        ("XLA Ops", [(3, 110, 20, []), (4, 130, 10, []), (5, 210, 30, [])]),
    ], [
        (1, "jit_paged_decode_step(7)", []), (2, "jit_scatter(9)", []),
        (3, fusion, [(1, 3, 7), (2, 5, "jit(paged_decode_step)/paged_lse/exp:")]),
        (4, "%copy-start = f32[] copy-start()", [(1, 3, 7)]),
        # the scatter's fusion shares the op's name but not its program; its
        # path is an interned string, a stat metadata entry of its own
        (5, fusion, [(1, 3, 9), (2, 7, 10)]),
    ], {1: "program_id", 2: "tf_op", 10: "jit(scatter)/scatter:"})
    host = _plane(2, "/host:CPU", [("python", [(1, 0, 1000, []), (2, 100, 60, [(3, 4, 12)])])],
                  [(1, "chipbench.window", []), (2, "serve.kv_writeback", [])], {3: "rid"})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(host + tpu + _field(4, "host"))
    assert phases.op_paths(path) == [{(7, fusion): "jit(paged_decode_step)/paged_lse/exp:",
                                      (9, fusion): "jit(scatter)/scatter:"}]
    ev = phases.load(path)
    assert ev["spans"] == [(0, 1000, "chipbench.window"), (100, 160, "serve.kv_writeback")]
    assert ev["span_args"] == [{}, {"rid": 12}]
    assert ev["devices"][0]["scopes"] == [(110, 130, "jit_paged_decode_step/paged_lse")]
    assert phases.program_id("jit_paged_decode_step(7)") == 7


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        phases.reduce({"devices": [], "spans": []}, "chipbench.window", "chipbench.step")


def _ancestor(spans, up, i, name):
    while i >= 0 and spans[i][2] != name:
        i = up[i]
    return i


def test_engine_phase_spans_in_a_cpu_trace(tmp_path):
    from repro.configs.registry import get_config
    from repro.core.kv_pool import KVPoolConfig
    from repro.models.transformer import LM
    from repro.serve.engine import MaintenanceConfig, Request, ServeEngine

    cfg = get_config("stablelm_1_6b").smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))
    pool_cfg = KVPoolConfig(
        num_blocks=64, block_size=8, kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        n_layers=cfg.n_layers, max_seqs=4, max_blocks_per_seq=8, blocks_per_arena=8,
        dtype="float32")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in (5, 9, 12)]

    def serve(trace_dir=None):
        # a watermark that always trips, so a maintenance pass runs every step
        eng = ServeEngine(model, params, pool_cfg,
                          maintenance=MaintenanceConfig(free_low=1.0, every=1))
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p, max_new=4 + rid))
        decoded = []
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            alive = True
            while alive:
                d0 = eng.tokens_decoded
                alive = eng.step()
                decoded.append(eng.tokens_decoded - d0)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        return {r.rid: r.out for r in eng.done}, decoded

    plain, _ = serve()
    traced, decoded = serve(str(tmp_path))
    assert traced == plain and len(plain) == len(prompts)

    ev = phases.load(phases.xplane_of(str(tmp_path)))
    order = sorted(range(len(ev["spans"])), key=lambda i: (ev["spans"][i][0], -ev["spans"][i][1]))
    spans = [ev["spans"][i] for i in order]
    args = [ev["span_args"][i] for i in order]
    up = phases.parents(spans)
    names = {n for _, _, n in spans}
    assert names >= {"serve.step", *PHASES}
    steps = [i for i, s in enumerate(spans) if s[2] == "serve.step"]
    assert len(steps) == len(decoded)
    for i, (_, _, name) in enumerate(spans):
        if name != "serve.step":
            assert _ancestor(spans, up, up[i], "serve.step") >= 0, name
        if name == "serve.prefill":
            assert spans[up[i]][2] == "serve.admit"
    # one write-back span per sequence decoded in the step
    for k, i in enumerate(steps):
        n = sum(1 for j, s in enumerate(spans)
                if s[2] == "serve.kv_writeback" and _ancestor(spans, up, j, "serve.step") == i)
        assert n == decoded[k]
    prefills = [a for s, a in zip(spans, args) if s[2] == "serve.prefill"]
    assert sorted(a["rid"] for a in prefills) == list(range(len(prompts)))
    assert all(a["tokens"] == len(prompts[a["rid"]]) for a in prefills)
    wb = [a["rid"] for s, a in zip(spans, args) if s[2] == "serve.kv_writeback"]
    assert sorted(set(wb)) == list(range(len(prompts)))
