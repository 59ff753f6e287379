"""95th percentile of every gap between consecutive tokens of one request
where both tokens landed in the window (host clock, ms)."""
from chipbench import window


def read(run):
    w = run["window"]
    p = window.p95(window.gaps_in_window(run["token_times"].values(), w["t0"], w["t1"]))
    return None if p is None else p * 1e3
