"""``Call.counts``: the change each engine call made to the numeric fields
of its stage engine's ``stats``, taken by the open-loop driver around one
admit and one fused decode step of the tiny cascade on the CPU."""
import time

import harness
import traffic as T
from conftest import tiny_cell
from driver import OpenLoop, numbers


def test_calls_hold_the_engines_stat_changes():
    from repro.serving.token_engine import TokenEngine
    cell, cfgs = tiny_cell()
    cfgs = harness.stage_configs(cell, cfgs)
    params = harness.make_weights(cfgs, 5)
    engines = harness.build_engines(cell, cfgs, params)
    gear = harness.cascade_gear([c.name for c in cfgs], (0.0,))
    te = TokenEngine(engines, gear, mode="fused", spec_k=1)
    # a count an engine may add later reaches the call with no edit to
    # the driver: here, one per fused step of stage 0
    eng0 = engines[0]
    eng0.stats.experts_read = 0
    step = te._step_fused

    def step_and_count(si, eng, *a, **k):
        out = step(si, eng, *a, **k)
        if eng is eng0:
            eng.stats.experts_read += 3
        return out

    te._step_fused = step_and_count
    start = [numbers(e.stats) for e in engines]
    arrivals = T.make_requests(cell.traffic, 11, 1.0)[:3]
    loop = OpenLoop(te, arrivals, time.perf_counter() - 100.0)
    while not any(c.kind == "decode" for c in loop.calls):
        loop.step()

    admit, decode = loop.calls[0], loop.calls[-1]
    assert (admit.kind, admit.stage, decode.kind, decode.stage) == (
        "admit", 0, "decode", 0)
    assert admit.rows == 3
    assert admit.counts["prefill_calls"] == 1
    assert admit.counts["prefill_prompts"] == 3
    assert admit.counts["bytes_to_host"] == 3 * 8    # (token, gap) each
    assert admit.counts["decode_calls"] == 0
    assert decode.counts["decode_calls"] == decode.counts["decode_steps"] \
        == 1
    assert decode.counts["bytes_to_host"] == 12 * eng0.n_slots
    assert decode.counts["prefill_calls"] == 0
    assert decode.counts["experts_read"] == 3
    assert "prefill_shapes" not in admit.counts
    # together the calls hold every change the engines' stats saw
    for si, eng in enumerate(engines):
        total = {}
        for c in loop.calls:
            if c.stage == si:
                for k, v in c.counts.items():
                    total[k] = total.get(k, 0) + v
        end = numbers(eng.stats)
        assert {k: v for k, v in total.items() if v} == {
            k: v - start[si].get(k, 0) for k, v in end.items()
            if v - start[si].get(k, 0)}
