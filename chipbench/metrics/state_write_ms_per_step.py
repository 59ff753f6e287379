"""Device time of the pool's jitted state write in the window, per window
step (ms): ``PagedKVPool.write_token_states``'s one write of the batch's
new Mamba2 states each decode step and ``write_prompt_states``'s one write
of a prompt's final states each prefill.  None where the program has no
such write."""

#: the write program's name in the trace's XLA Modules line
PROGRAM = "jit__write_states"


def read(run):
    t = run["trace"]
    if t is None or not t["steps"] or PROGRAM not in t["programs"]:
        return None
    return t["programs"][PROGRAM]["ns"] / t["steps"] / 1e6
