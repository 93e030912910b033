"""Median wait, in ms, from a request's due time to the start of the
stage-0 admit call that takes it, over the requests due in the window."""
import statistics


def read(run):
    waits = [r.admit_start[0] - r.due for r in run.window_records()
             if 0 in r.admit_start]
    return 1e3 * statistics.median(waits) if waits else None
