"""Generated tokens delivered in the window over the window's elapsed time
(host clock; the window ends on a step boundary)."""
from chipbench import window


def read(run):
    w = run["window"]
    n = window.tokens_in_window(run["token_times"].values(), w["t0"], w["t1"])
    return n / (w["t1"] - w["t0"])
