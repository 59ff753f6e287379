"""Wall time from process start to the first step of the window: weights,
pool, loading or compiling programs, and filling the batch."""


def read(run):
    return run["setup_s"]
