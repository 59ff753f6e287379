"""Device time of the jitted paged decode step per window step (ms)."""

#: the program's name in the trace's XLA Modules line
PROGRAM = "jit_paged_decode_step"


def read(run):
    t = run["trace"]
    if t is None or not t["steps"] or PROGRAM not in t["programs"]:
        return None
    return t["programs"][PROGRAM]["ns"] / t["steps"] / 1e6
