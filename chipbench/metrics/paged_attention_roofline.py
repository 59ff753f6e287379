"""Least time of the window's paged-attention calls over their device time
(%).  Least time is the larger of FLOPs over peak and bytes over bandwidth,
counted by the family over its attention layers from the live lengths of
each decode step: what the algorithm needs, not what the grid reads."""
from chipbench import counts

#: op name of the Pallas kernel in the trace's XLA Ops line
KERNEL = "paged_attention"


def read(run):
    t = run["trace"]
    if t is None or not t["ops"].get(KERNEL) or run["peaks"] is None:
        return None
    spec, family = run["spec"], run["family"]
    flops = nbytes = 0.0
    for step in run["steps"]:
        c = family.paged_attention_model(spec, step["past_lens"])
        flops += c["flops"]
        nbytes += c["bytes"]
    least = counts.roofline_seconds(flops, nbytes, run["peaks"])
    return 100.0 * least / (t["ops"][KERNEL] / 1e9)
