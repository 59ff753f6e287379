"""Device idle time inside the harness's span around each ``step()`` of the
window, per step (ms): the engine's host work the device waits for."""


def read(run):
    t = run["trace"]
    if t is None or not t["steps"]:
        return None
    return t["idle_in_steps_ns"] / t["steps"] / 1e6
