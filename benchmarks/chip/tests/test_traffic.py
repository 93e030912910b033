"""Every run seed serves the same work: the requests of each part of the
run (lead-in, window, drain) are fixed by the mix; the seed orders them."""
import json

import numpy as np
import pytest

from conftest import CHIP

SECONDS = 50


@pytest.fixture(scope="module")
def mix():
    return json.loads((CHIP / "traffic/chat-cascade.json").read_text())


def window(arrivals, mix):
    lead = mix["lead_in_s"]
    return [a for a in arrivals if lead <= a.due < lead + SECONDS]


def by_key(arrivals):
    return {a.key: (a.prompt.tolist(), a.max_new) for a in arrivals}


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2**31 + 11)])
def test_seeds_serve_the_same_requests_in_another_order(mix, seeds):
    import traffic as T
    a, b = (T.make_requests(mix, s, SECONDS) for s in seeds)
    wa, wb = window(a, mix), window(b, mix)
    assert len(wa) == len(wb) == round(mix["rate_rps"] * SECONDS)
    assert by_key(wa) == by_key(wb)
    assert by_key(a) == by_key(b)
    assert [x.key for x in wa] != [x.key for x in wb]
    assert [x.due for x in wa] != [x.due for x in wb]


def test_parts_keep_their_arrivals(mix):
    import traffic as T
    a = T.make_requests(mix, 3, SECONDS)
    dues = np.array([x.due for x in a])
    assert np.all(np.diff(dues) > 0)
    assert [x.rid for x in a] == list(range(len(a)))
    for start, length, n in T.parts(mix, SECONDS):
        inside = (dues >= start) & (dues < start + length)
        assert inside.sum() == n


def test_probe_is_fixed_and_unlike_the_measured_requests(mix):
    import traffic as T
    p, q = T.probe_requests(mix, 16), T.probe_requests(mix, 16)
    assert by_key(p) == by_key(q)
    measured = {tuple(x.prompt.tolist())
                for x in T.make_requests(mix, 5, SECONDS)}
    assert not measured & {tuple(x.prompt.tolist()) for x in p}
