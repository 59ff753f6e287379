"""Device time of the pool's eager write-back programs in the window, per
decode step (ms): the ``.at[].set`` copies of ``PagedKVPool.write_token_kv``
and ``write_prompt_kv`` and the small programs that slice and index for
them."""

#: eager programs of the write-back, read by hand from a v5e trace of the
#: stablelm-1.6b and chatglm3-6b cells
PROGRAMS = ("jit_scatter", "jit_convert_element_type", "jit_squeeze", "jit__squeeze",
            "jit_dynamic_slice", "jit_concatenate", "jit_broadcast_in_dim", "jit_pad",
            "jit_reshape")


def read(run):
    t = run["trace"]
    if t is None or not t["steps"]:
        return None
    ns = sum(t["programs"].get(p, {"ns": 0.0})["ns"] for p in PROGRAMS)
    return ns / t["steps"] / 1e6
