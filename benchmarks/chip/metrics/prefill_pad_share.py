"""Share of the prefilled tokens that were padding: 1 - real prompt tokens
over padded tokens, summed over the program's ``slot.prefill`` phases
(their ``tokens`` and ``padded`` counts) that end inside the window."""
import spans


def read(run):
    if run.telemetry is None:
        return None
    return spans.prefill_pad_share(run.telemetry.phases, run.win0, run.win1)
