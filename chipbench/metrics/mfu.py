"""Model FLOPs of every prompt and generated token the window processed,
attention included (``counts.py``), over the traced window times the
chip's peak bf16 FLOP/s (%)."""
from chipbench import counts


def read(run):
    t = run["trace"]
    if t is None or run["peaks"] is None:
        return None
    spec = run["spec"]
    flops = 0.0
    for step in run["steps"]:
        flops += sum(counts.decode_token_flops(spec, n + 1) for n in step["past_lens"])
        flops += sum(counts.prefill_flops(spec, p) for p in step["prefills"])
    return 100.0 * flops / (t["window_ns"] / 1e9 * run["peaks"]["bf16_flops_per_s"])
