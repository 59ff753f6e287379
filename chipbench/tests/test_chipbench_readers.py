"""Metric readers over a hand-made reduced trace: the pool's write programs
per step, and the readers that take their counts from the run's family."""
from types import SimpleNamespace

import pytest

from chipbench.metrics import mfu, paged_attention_roofline, pool_write_ms_per_step

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def reduced(programs, steps=4, ops=None, window_ns=2e9):
    return {"programs": programs, "steps": steps, "ops": ops or {}, "window_ns": window_ns}


def test_pool_write_sums_both_write_programs_per_step():
    t = reduced({"jit__write_tokens": {"ns": 3e6, "n": 4}, "jit__write_pages": {"ns": 5e6, "n": 1},
                 "jit_paged_decode_step": {"ns": 90e6, "n": 4}, "jit_scatter": {"ns": 7e6, "n": 2}})
    assert pool_write_ms_per_step.read({"trace": t}) == pytest.approx(2.0)
    # a window with no prefill has no page write
    t = reduced({"jit__write_tokens": {"ns": 3e6, "n": 4}})
    assert pool_write_ms_per_step.read({"trace": t}) == pytest.approx(0.75)


@pytest.mark.parametrize("t", [None, reduced({"jit_paged_decode_step": {"ns": 1.0, "n": 1}}),
                               reduced({"jit__write_tokens": {"ns": 1.0, "n": 1}}, steps=0)])
def test_pool_write_reads_nothing_without_its_programs(t):
    assert pool_write_ms_per_step.read({"trace": t}) is None


def test_counts_come_from_the_runs_family():
    family = SimpleNamespace(
        decode_token_flops=lambda spec, ctx: 1e9 * ctx,
        prefill_flops=lambda spec, p: 1e10 * p,
        paged_attention_model=lambda spec, lens: {"flops": 0.0, "bytes": 1e6 * sum(lens)})
    run = {"spec": {}, "family": family, "peaks": PEAKS,
           "steps": [{"past_lens": [1, 2], "prefills": [3]}, {"past_lens": [4], "prefills": []}],
           "trace": reduced({}, ops={"paged_attention": 14e6})}
    # decode contexts 2, 3, 5 and one prefill of 3: 1e10 + 3e10 FLOPs over 2 s x 1e12
    assert mfu.read(run) == pytest.approx(2.0)
    # 7 cached tokens: 7e6 B at 1e9 B/s is 7 ms of the kernel's 14 ms
    assert paged_attention_roofline.read(run) == pytest.approx(50.0)
