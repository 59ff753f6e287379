"""Window arithmetic: rates over whole steps, the tail over every gap."""
import numpy as np
import pytest

from chipbench import window
from chipbench.metrics import itl_p95_ms, output_tokens_per_s


def simulate(step_s, seconds, batch):
    """A closed loop whose steps take ``step_s`` in turn: returns the
    window's run record as run.py builds it."""
    t0, t, times, i = 0.0, 0.0, [[] for _ in range(batch)], 0
    while True:
        t += step_s[i % len(step_s)]
        i += 1
        for b in range(batch):
            times[b].append(t)
        if window.closes(t, t0, seconds):
            break
    return {"window": {"t0": t0, "t1": t}, "token_times": dict(enumerate(times))}, i


def test_window_ends_on_the_first_step_past_the_length():
    run, steps = simulate([2.0, 3.0], seconds=10.0, batch=2)
    # steps end at 2, 5, 7, 10: the step ending at 10 closes it, none is cut
    assert steps == 4 and run["window"]["t1"] == 10.0
    run, steps = simulate([2.0, 3.0], seconds=10.5, batch=2)
    assert steps == 5 and run["window"]["t1"] == 12.0


def test_tokens_per_s_counts_whole_steps_over_elapsed_time():
    run, steps = simulate([2.0, 3.0], seconds=10.5, batch=4)
    assert output_tokens_per_s.read(run) == pytest.approx(4 * 5 / 12.0)


def test_p95_is_over_every_gap_not_a_median_of_chunks():
    times = {0: [1.0, 2.0, 3.0, 13.0], 1: [1.0, 1.5, 2.0, 2.5, 3.0, 3.5]}
    run = {"window": {"t0": 0.0, "t1": 20.0}, "token_times": times}
    gaps = [1.0, 1.0, 10.0] + [0.5] * 5
    assert itl_p95_ms.read(run) == pytest.approx(np.percentile(gaps, 95) * 1e3)


def test_gaps_need_both_tokens_inside_the_window():
    times = [[-1.0, 0.5, 1.0, 9.0, 12.0]]
    assert window.gaps_in_window(times, 0.0, 10.0) == [0.5, 8.0]
    assert window.tokens_in_window(times, 0.0, 10.0) == 3
    assert window.p95([]) is None
