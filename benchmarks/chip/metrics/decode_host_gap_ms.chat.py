"""Mean device idle time, in ms, inside one of the program's
``engine.decode`` phases (dispatch, fetch and the cascade's decision
included), over the fused decode calls that the traced sub-window holds
whole."""
import spans


def read(run):
    if not run.trace or "program_calls" not in run.trace:
        return None
    return spans.host_gap_ms(run.trace, "engine.decode")
