"""Share, in %, of the HBM roofline that the top-2-gap kernel reaches in
the traced sub-window: the logits bytes its calls must read (one call per
fused decode step over every slot, one per prefill over its batch
bucket) over the bandwidth, against the device time of the kernel's
operations in the same calls. Only calls that the trace holds whole
count, on both sides."""
import costs
import tracing

# the Pallas kernel is the program's only Mosaic custom call
NEEDLES = ("top2gap", "tpu_custom_call")


def read(run):
    calls = run.traced_calls()
    device = sum(tracing.kernel_seconds(run.trace, NEEDLES, span)[0]
                 for _, span in calls)
    if device <= 0:
        return None
    nbytes = sum(costs.top2gap_bytes(c.padded_rows, run.archs[c.stage].vocab)
                 for c, _ in calls)
    return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / device
