"""Share of the window's wall time spent inside admit calls that
prefilled (every stage)."""


def read(run):
    return sum(run.overlap(c) for c in run.calls if c.kind == "admit") \
        / run.seconds
