"""Mean device idle time, in ms, inside one of the program's
``engine.admit`` phases (its prefill, joins and fetches included), over
the admits that the traced sub-window holds whole."""
import spans


def read(run):
    if not run.trace or "program_calls" not in run.trace:
        return None
    return spans.host_gap_ms(run.trace, "engine.admit")
