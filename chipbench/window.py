"""Arithmetic of the measured window, shared by the metric readers.

Every token carries the host time at which ``ServeEngine.step()`` returned
it.  The window opens before its first step and closes at the end of the
first step that returns after ``--seconds``, then waits for the device to
finish that step's work, so a window never cuts a step in two.
"""
from __future__ import annotations

from typing import Dict, Iterable, List

import numpy as np


def closes(step_end: float, t0: float, seconds: float) -> bool:
    """True when the step that returned at ``step_end`` is the window's last."""
    return step_end - t0 >= seconds


def tokens_in_window(token_times: Iterable[List[float]], t0: float, t1: float) -> int:
    return sum(1 for times in token_times for t in times if t0 <= t <= t1)


def gaps_in_window(token_times: Iterable[List[float]], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive tokens of one request where both tokens
    landed in the window (a prefill's token and the same step's decode
    token land together: a gap of 0)."""
    out: List[float] = []
    for times in token_times:
        inside = [t for t in times if t0 <= t <= t1]
        out.extend(b - a for a, b in zip(inside, inside[1:]))
    return out


def p95(values: List[float]) -> float | None:
    """95th percentile (linear interpolation); None with no samples."""
    return float(np.percentile(np.asarray(values, float), 95)) if values else None

