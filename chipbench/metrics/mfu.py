"""Model FLOPs of every prompt and generated token the window processed,
attention included (the family's counts), over the traced window times the
chip's peak bf16 FLOP/s (%)."""


def read(run):
    t = run["trace"]
    if t is None or run["peaks"] is None:
        return None
    spec, family = run["spec"], run["family"]
    flops = 0.0
    for step in run["steps"]:
        flops += sum(family.decode_token_flops(spec, n + 1) for n in step["past_lens"])
        flops += sum(family.prefill_flops(spec, p) for p in step["prefills"])
    return 100.0 * flops / (t["window_ns"] / 1e9 * run["peaks"]["bf16_flops_per_s"])
