"""Share of the traced sub-window in which no operation ran on the
device."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
