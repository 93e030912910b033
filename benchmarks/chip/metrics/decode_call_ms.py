"""Mean wall time, in ms, of one fused decode call (every stage) in the
window: the host's view of a decode step, dispatch and transfer
included."""


def read(run):
    calls = run.window_calls("decode")
    if not calls:
        return None
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / len(calls)
