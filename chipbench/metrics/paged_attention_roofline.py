"""Least time of the window's paged-attention calls over their device time
(%).  Least time is the larger of FLOPs over peak and bytes over bandwidth,
counted from the live lengths of each decode step (``counts.py``): what
the algorithm needs, not what the grid reads."""
from chipbench import counts

#: op name of the Pallas kernel in the trace's XLA Ops line
KERNEL = "paged_attention"


def read(run):
    t = run["trace"]
    if t is None or not t["ops"].get(KERNEL) or run["peaks"] is None:
        return None
    spec = run["spec"]
    flops = nbytes = 0.0
    for step in run["steps"]:
        c = counts.paged_attention(spec, step["past_lens"])
        flops += spec["layers"] * c["flops"]
        nbytes += spec["layers"] * c["bytes"]
    least = counts.roofline_seconds(flops, nbytes, run["peaks"])
    return 100.0 * least / (t["ops"][KERNEL] / 1e9)
