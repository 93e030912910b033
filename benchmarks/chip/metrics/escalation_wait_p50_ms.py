"""Median wait, in ms, from a request's escalation (the engine's
``escalate`` event) to the start of the next stage's admit that takes it
(its ``fire``), over the escalations of the requests due in the window,
from the program's request events (``TokenEngine`` telemetry, on in a
traced run)."""
import statistics

import spans


def read(run):
    if run.telemetry is None:
        return None
    waits = spans.escalation_wait_ms(
        run.telemetry.spans, [r.arrival.rid for r in run.window_records()])
    return statistics.median(waits) if waits else None
