"""The knee that sets a chat mix's rate, from a sweep's rows."""
import pytest

import sweep


def row(seed, rate, first, last):
    return {"seed": seed, "rate_rps": rate, "queue_first_third": first,
            "queue_last_third": last}


def test_knee_is_the_highest_rate_no_seed_grew_at_or_below():
    rows = [row(1, 2.0, 0.1, 0.3), row(2, 2.0, 0.0, 0.2),
            # a burst that cleared before the last third
            row(1, 3.0, 2.0, 0.4), row(2, 3.0, 0.5, 0.8),
            # a queue that stood from the start, and one that grew
            row(1, 4.0, 3.9, 3.7), row(2, 4.0, 0.2, 2.5),
            # a rate above one that grew does not count, queue or not
            row(1, 5.0, 0.1, 0.1), row(2, 5.0, 0.1, 0.1)]
    assert [sweep.grows(r) for r in rows] == [
        False, False, False, False, True, True, False, False]
    assert sweep.knee(rows) == 3.0


def test_no_knee_when_every_rate_grew():
    with pytest.raises(ValueError):
        sweep.knee([row(1, 2.0, 0.0, 5.0)])
