"""JAX programs compiled or read from the persistent cache inside the
window (JAX monitoring events); 0 when set-up warmed every shape."""


def read(run):
    return run["compiles_in_window"]
