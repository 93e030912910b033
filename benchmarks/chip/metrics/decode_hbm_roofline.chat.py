"""Share, in %, of the roofline that the fused decode executables reach in
the traced sub-window: the least time their calls need (the larger of
FLOPs over peak and bytes over HBM bandwidth, counted by each stage model's
family from the call) over the device time of the same calls' executables.
The bytes are the weights, the live KV of the active rows and the new KV
written, whatever implements the step. Only calls that the trace holds
whole count, on both sides."""
import costs
import tracing

MODULE = "fused_decode"


def read(run):
    calls = run.traced_calls("decode")
    device = sum(tracing.module_seconds(run.trace, MODULE, span)
                 for _, span in calls)
    if device <= 0:
        return None
    ideal = sum(costs.ideal_seconds(*run.families[c.stage].decode_call(
        run.archs[c.stage], c), run.peak) for c, _ in calls)
    return 100.0 * ideal / device
