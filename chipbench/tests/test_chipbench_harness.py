"""The harness end to end on the CPU at a tiny size: files found by name,
a family added as one new file, the CPU refused, and ``correct`` false
when the timed path is broken or when the fp8 control takes the program's
place."""
import json

import jax.numpy as jnp
import pytest

import tinytree
from chipbench import run, weights

#: tiny limit, from CPU readings over seven seeds, every served request
#: compared: program widest gap at most 0.0253, fp8 control at least 0.1813
TINY_LIMIT = 0.05
SEED = 2**31 + 11


def run_tiny(tmp_path, **kw):
    root = tinytree.build(tmp_path, TINY_LIMIT, kw.pop("extra_metric", None))
    return run.run_cell(root, tinytree.CELL, SEED, 1.0, False, require_chip=False, **kw)


def test_new_config_mix_and_metric_are_found_by_name(tmp_path, no_compile_cache):
    r = run_tiny(tmp_path, extra_metric="requests_served")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"output_tokens_per_s", "itl_p99_ms", "setup_s",
                                 "requests_served"}
    assert r["metrics"]["requests_served"]["value"] >= 4
    assert r["checks"]["tokens_compared"]["value"] >= 16
    assert list(r)[-1] == "checks" and r["failed"] == 0


def test_cpu_is_refused_before_any_work(tmp_path, monkeypatch, capsys):
    root = tinytree.build(tmp_path, TINY_LIMIT)
    monkeypatch.chdir(root)
    monkeypatch.setattr(weights, "make_weights", lambda *_: pytest.fail("work began on the CPU"))
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", tinytree.CELL, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


#: a second family, added as one file: the dense family's functions, each
#: call logged beside the module
PROBE = '''
import functools
from pathlib import Path

from chipbench.families import dense

LOG = Path(__file__).with_name("probe_calls.txt")


def _logged(f):
    @functools.wraps(f)
    def call(*a, **k):
        with LOG.open("a") as fh:
            fh.write(f.__name__ + "\\n")
        return f(*a, **k)
    return call


FAN_IN = dense.FAN_IN
program_config = _logged(dense.program_config)
pool_config = _logged(dense.pool_config)
weight_shapes = _logged(dense.weight_shapes)
program_params = _logged(dense.program_params)
forward_logits = _logged(dense.forward_logits)
decode_token_flops = _logged(dense.decode_token_flops)
prefill_flops = _logged(dense.prefill_flops)
paged_attention_model = _logged(dense.paged_attention_model)
'''


def name_family(root, family):
    path = root / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))


def test_a_family_added_as_one_file_is_the_one_used(tmp_path, no_compile_cache):
    root = tinytree.build(tmp_path, TINY_LIMIT)
    (root / "bench" / "families" / "probe.py").write_text(PROBE)
    name_family(root, "probe")
    r = run.run_cell(root, tinytree.CELL, SEED, 1.0, False, require_chip=False)
    assert r["correct"], r["checks"]
    calls = (root / "bench" / "families" / "probe_calls.txt").read_text().split()
    assert set(calls) == {"program_config", "pool_config", "weight_shapes", "program_params",
                          "forward_logits"}
    assert [calls.count(f) for f in ("program_config", "pool_config", "weight_shapes")] == [1, 1, 1]


@pytest.mark.parametrize("family, looked_for", [("nosuch", "families/nosuch.py"),
                                                (None, "families/<family>.py")])
def test_unknown_family_exits_before_any_work(tmp_path, monkeypatch, family, looked_for):
    root = tinytree.build(tmp_path, TINY_LIMIT)
    name_family(root, family)
    monkeypatch.setattr(weights, "make_weights", lambda *_: pytest.fail("work began"))
    with pytest.raises(SystemExit) as e:
        run.run_cell(root, tinytree.CELL, SEED, 1.0, False, require_chip=False)
    assert looked_for in str(e.value) and "tiny.json" in str(e.value)


def _roll_tokens(step):
    def broken(*a, **k):
        logits, k1, v1 = step(*a, **k)
        return jnp.roll(logits, 1, axis=-1), k1, v1
    return broken


def _half_batch(step):
    def broken(*a, **k):
        logits, k1, v1 = step(*a, **k)
        h = logits.shape[0] // 2
        return logits.at[logits.shape[0] - h:].set(logits[:h]), k1, v1
    return broken


@pytest.mark.parametrize("fault", ["token_altered", "kv_write_dropped", "half_batch"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, no_compile_cache, fault):
    import repro.serve.engine as engine
    from repro.core.kv_pool import PagedKVPool

    if fault == "token_altered":
        monkeypatch.setattr(engine, "paged_decode_step_jit", _roll_tokens(engine.paged_decode_step_jit))
    elif fault == "half_batch":
        monkeypatch.setattr(engine, "paged_decode_step_jit", _half_batch(engine.paged_decode_step_jit))
    else:
        monkeypatch.setattr(PagedKVPool, "write_token_kv", lambda *a, **k: None)
    r = run_tiny(tmp_path)
    assert not r["correct"]
    assert r["checks"]["logit_gap_max"]["value"] > TINY_LIMIT
    json.dumps(r)


def test_fp8_control_fails_where_the_program_passes(tmp_path, no_compile_cache):
    r = run_tiny(tmp_path, control=True)
    assert not r["correct"]
    assert r["checks"]["logit_gap_max"]["value"] == r["readings"]["control_gap_max"] > TINY_LIMIT
    assert r["readings"]["program_gap_max"] <= TINY_LIMIT
