"""Device time of the pool's jitted write programs in the window, per
window step (ms): ``PagedKVPool.write_token_kv``'s one write of the batch's
new tokens each decode step and ``write_prompt_kv``'s one write of a
prompt's pages each prefill."""

#: the write programs' names in the trace's XLA Modules line
PROGRAMS = ("jit__write_tokens", "jit__write_pages")


def read(run):
    t = run["trace"]
    if t is None or not t["steps"] or not any(p in t["programs"] for p in PROGRAMS):
        return None
    return sum(t["programs"][p]["ns"] for p in PROGRAMS if p in t["programs"]) / t["steps"] / 1e6
