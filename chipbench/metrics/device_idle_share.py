"""Share of the window in which no operation ran on the device (%)."""


def read(run):
    t = run["trace"]
    return None if t is None else 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])
