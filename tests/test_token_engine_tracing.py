"""TokenEngine telemetry on the caller's clock (DESIGN.md §16): request
events and phase spans observe without moving a decision, nest as the
engine's calls do, and cost nothing (no annotation, no clock read) when
telemetry is off."""
import itertools
from collections import deque

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import telemetry as telemetry_mod
from repro.core.cascade import Cascade
from repro.core.gears import Gear
from repro.core.telemetry import Telemetry
from repro.models import model as M
from repro.serving.token_engine import (SlotEngine, TokenEngine,
                                        TokenRequest, TokenResult)

ADMIT_CHILDREN = ["slot.prefill", "slot.join", "slot.fetch", "slot.join"]
DECODE_CHILDREN = ["slot.dispatch", "slot.fetch", "engine.decide"]


@pytest.fixture(scope="module")
def cascade():
    cfg = get_smoke_config("qwen2-0.5b")
    pa = M.init_params(cfg, jax.random.PRNGKey(0))
    pb = M.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(3)
    reqs = [TokenRequest(i, rng.integers(0, cfg.vocab_size,
                                         6 + 5 * i).astype(np.int32), 6)
            for i in range(5)]
    return cfg, pa, pb, reqs


def fake_clock():
    ticks = itertools.count()
    return lambda: float(next(ticks))


def make_engine(cascade, threshold, **kw):
    cfg, pa, pb, _ = cascade
    gear = Gear(cascade=Cascade(("a", "b"), (threshold,)),
                min_queue_lens={"a": 1, "b": 1},
                load_fractions={"a": {0: 1.0}, "b": {1: 1.0}})
    stages = [SlotEngine("a", pa, cfg, n_slots=2, max_len=48),
              SlotEngine("b", pb, cfg, n_slots=2, max_len=48)]
    return TokenEngine(stages, gear, min_tokens=2, **kw)


def drive(te, reqs):
    """The benchmark driver's way: one request arrives every other token
    boundary into ``waiting[0]``, then each stage admits and decodes."""
    waiting = [deque() for _ in te.stages]
    act = [[] for _ in te.stages]
    pending, results, b = deque(reqs), {}, 0
    while pending or any(waiting) or any(act):
        if pending and b % 2 == 0:
            r = pending.popleft()
            results[r.rid] = TokenResult(rid=r.rid)
            waiting[0].append((r, results[r.rid]))
        for si, eng in enumerate(te.stages):
            te._admit(si, eng, waiting, act, b)
            if act[si]:
                te._step_fused(si, eng, waiting, act, b)
        b += 1
    return results


def run(te, reqs, how):
    return te.serve(reqs) if how == "serve" else drive(te, reqs)


def outcome(results):
    return {rid: (r.tokens, r.gaps, r.resolver, r.hops, r.first_token_step,
                  r.done_step, r.stage_gaps)
            for rid, r in sorted(results.items())}


@pytest.fixture(scope="module")
def threshold(cascade):
    """A stage-0 threshold under which some requests escalate and some
    resolve at stage 0: the median final certainty of stage 0 alone."""
    from repro.core.certainty import StreamingCertainty
    cfg, pa, _, reqs = cascade
    eng = SlotEngine("a", pa, cfg, n_slots=2, max_len=48)
    gear = Gear(cascade=Cascade(("a",), ()), min_queue_lens={"a": 1},
                load_fractions={"a": {0: 1.0}})
    out = TokenEngine([eng], gear, min_tokens=2).serve(reqs)
    finals = []
    for r in reqs:
        cert = StreamingCertainty(mode="ewma", beta=0.35)
        for g in out[r.rid].gaps:
            cert.update(g)
        finals.append(cert.value)
    return float(np.median(finals))


@pytest.fixture(scope="module", params=["serve", "boundary"])
def traced(request, cascade, threshold):
    """The same cascade run with telemetry off and on (fake clock)."""
    how, reqs = request.param, cascade[3]
    off = run(make_engine(cascade, threshold), reqs, how)
    telem = Telemetry()
    te = make_engine(cascade, threshold, telemetry=telem, clock=fake_clock())
    on = run(te, reqs, how)
    return how, off, on, te, telem


def test_telemetry_leaves_decisions_and_tokens_bit_identical(traced):
    how, off, on, te, telem = traced
    assert outcome(on) == outcome(off)
    hops = [r.hops for r in on.values()]
    # the threshold splits the set: both outcomes are exercised
    assert 0 in hops and any(h > 0 for h in hops), hops
    assert telem.phases and all(e.telemetry is telem for e in te.stages)


def test_phases_nest_and_cover_every_engine_call(traced):
    how, _, on, te, telem = traced
    phases = telem.phases
    assert [p.n for p in phases] == list(range(len(phases)))
    for p in phases:
        assert p.t0 < p.t1
        if p.parent >= 0:
            up = phases[p.parent]
            assert up.t0 < p.t0 and p.t1 < up.t1, (p.name, up.name)
            assert (p.stage, p.boundary) == (up.stage, up.boundary)
    children = {p.n: [c.name for c in phases if c.parent == p.n]
                for p in phases}
    for si, eng in enumerate(te.stages):
        decodes = [p for p in phases
                   if p.name == "engine.decode" and p.stage == si]
        admits = [p for p in phases
                  if p.name == "engine.admit" and p.stage == si]
        assert len(decodes) == eng.stats.decode_calls > 0
        assert len(admits) == eng.stats.prefill_calls > 0
        for p in decodes:
            assert children[p.n] == DECODE_CHILDREN
            assert p.counts["k"] == 1 and 1 <= p.counts["rows"] <= 2
        for p in admits:
            assert children[p.n] == ADMIT_CHILDREN
        prefills = [p for p in phases
                    if p.name == "slot.prefill" and p.stage == si]
        assert sum(p.counts["rows"] for p in prefills) \
            == eng.stats.prefill_prompts
        for p in prefills:
            c = p.counts
            assert c["padded"] == c["batch_bucket"] * c["len_bucket"]
            assert c["rows"] <= c["batch_bucket"]
            assert c["rows"] <= c["tokens"] <= c["padded"]
    # the engine's calls do not overlap
    tops = [p for p in phases if p.parent < 0]
    assert all(a.t1 < b.t0 for a, b in zip(tops, tops[1:]))
    decides = [p for p in phases if p.name == "engine.decide"]
    assert sum(p.counts["escalations"] for p in decides) == \
        sum(r.hops for r in on.values())
    assert sum(p.counts["leaves"] for p in decides) == \
        sum(r.hops + 1 for r in on.values())


def test_request_events_on_the_clock(traced):
    how, _, on, _, telem = traced
    admit_at = {ev[2]: ev[1] for ev in telem.raw if ev[0] == "admit"}
    fires = [ev for ev in telem.raw if ev[0] == "fire"]
    first_fire = {}
    for _, t, stage, rids in fires:
        for rid in rids:
            first_fire.setdefault(rid, t)
    starts = {p.t0 for p in telem.phases if p.name == "engine.admit"}
    assert {t for _, t, _, _ in fires} <= starts
    if how == "serve":
        # custody at queue entry: every admit before the first fire
        assert max(admit_at.values()) < min(first_fire.values())
    else:
        # custody at the first stage-0 admit: the same instant as its fire
        assert admit_at == first_fire
    telem.finalize()
    assert telem.conservation()["completed"] == len(on)
    n_escalated = 0
    for sid, span in telem.spans.items():
        assert span.t_admit <= span.events[0][1]
        assert span.events[-1][1] < span.t_close
        evs = span.events
        for i, (kind, t, stage) in enumerate(evs):
            if kind != "escalate":
                continue
            n_escalated += 1
            fire = next(e for e in evs[i + 1:]
                        if e[0] == "fire" and e[2] == stage + 1)
            assert fire[1] > t
    assert n_escalated == sum(r.hops for r in on.values()) > 0


def test_telemetry_off_builds_no_annotation_and_reads_no_clock(
        cascade, threshold, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a TraceAnnotation was built")

    def no_clock():
        raise AssertionError("the clock was read")

    monkeypatch.setattr(telemetry_mod, "TraceAnnotation", refuse)
    reqs = cascade[3]
    for how in ("serve", "boundary"):
        te = make_engine(cascade, threshold, clock=no_clock)
        out = run(te, reqs, how)
        assert len(out) == len(reqs)
        assert all(e.telemetry is None for e in te.stages)


def test_phases_annotate_the_profiler_with_their_number(cascade, threshold,
                                                        monkeypatch):
    made = []

    class Annotation:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(telemetry_mod, "TraceAnnotation", Annotation)
    telem = Telemetry()
    te = make_engine(cascade, threshold, telemetry=telem, clock=fake_clock())
    drive(te, cascade[3])
    assert made == [(p.name, {"n": p.n, "s": p.stage, "b": p.boundary})
                    for p in telem.phases]
    assert not telem.registry.family("engine_ttft_steps")
    assert not telem.registry.family("engine_tpot_steps")
