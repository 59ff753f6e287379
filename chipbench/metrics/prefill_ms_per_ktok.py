"""Device time of the prefill program per 1000 prompt tokens, over every
prefill of the trace (the trace starts before set-up fills the batch)."""

#: ``ServeEngine._prefill`` runs the model's jitted ``decode_step``
PROGRAM = "jit_decode_step"


def read(run):
    t = run["trace"]
    tokens = sum(sum(s["prefills"]) for s in run["fill"] + run["steps"])
    if t is None or not tokens or PROGRAM not in t["programs_all"]:
        return None
    return t["programs_all"][PROGRAM]["ns"] / 1e6 / (tokens / 1000.0)
