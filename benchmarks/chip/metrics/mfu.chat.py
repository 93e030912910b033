"""Model FLOP/s utilization, in %: the model FLOPs of the real (unpadded)
prompt and decode tokens of the calls that the traced sub-window holds
whole (each stage model's family counts them from the call), over the
sub-window's length times the chip's bf16 peak."""


def read(run):
    calls = run.traced_calls()
    if not calls or run.trace["window_s"] <= 0:
        return None
    flops = sum(run.families[c.stage].call_flops(run.archs[c.stage], c)
                for c, _ in calls)
    return 100.0 * flops / (run.trace["window_s"] * run.peak["bf16_flops"])
