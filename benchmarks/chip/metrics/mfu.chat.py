"""Model FLOP/s utilization, in %: the model FLOPs of the real (unpadded)
prompt and decode tokens of the calls that the traced sub-window holds
whole, over the sub-window's length times the chip's bf16 peak."""
import costs


def read(run):
    calls = run.traced_calls()
    if not calls or run.trace["window_s"] <= 0:
        return None
    flops = 0.0
    for c, _ in calls:
        a = run.archs[c.stage]
        if c.kind == "admit":
            flops += sum(costs.prefill_flops(a, n) for n in c.prompt_lens)
        else:
            flops += sum(costs.token_flops(a, d + 1) for d in c.depths)
    return 100.0 * flops / (run.trace["window_s"] * run.peak["bf16_flops"])
