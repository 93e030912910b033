"""The program's phase spans beside the device trace (``spans.py``): a
trace made by hand and counted by hand, the slice recorded on a v5e chip
with the in-memory records of the same phases, and a traced run of the
tiny cascade on the CPU."""
import json
from pathlib import Path

import pytest

import spans
import tracing
from conftest import tiny_cell

DATA = Path(__file__).resolve().parent / "data"


def _ev(name, start, dur, **kw):
    return {"name": name, "start_ns": start, "dur_ns": dur, **kw}


def handmade():
    """Driver phases over 0-24000 ns; one decode and one admit of the
    program held whole, and a decode cut by the window's start."""
    device = [{"plane": "/device:TPU:0", "line": "XLA Ops", "name": n,
               "start_ns": a, "dur_ns": b - a, "module": "", "long": ""}
              for n, a, b in [("fusion.1", 900, 7900),
                              ("fusion.2", 12500, 16000),
                              ("fusion.3", 16500, 17500),
                              ("fusion.4", 20500, 21000)]]
    device.append({"plane": "/device:TPU:0", "line": "XLA Modules",
                   "name": "jit_fused_decode(1)", "start_ns": 900,
                   "dur_ns": 7000, "module": "", "long": ""})
    host = [_ev("decode.s0", 0, 10000), _ev("bookkeeping", 10000, 1000),
            _ev("admit.s1", 11000, 9000), _ev("wait_arrival", 20000, 4000)]
    program = [(0, "engine.decode", -500, 50, 1),
               (1, "slot.dispatch", -400, 0, 1),
               (2, "engine.decode", 100, 9900, 0),
               (3, "slot.dispatch", 200, 1000, 0),
               (4, "slot.fetch", 1100, 8000, 0),
               (5, "engine.decide", 8100, 9800, 0),
               (6, "engine.admit", 11100, 19900, 1),
               (7, "slot.prefill", 11200, 12000, 1),
               (8, "slot.join", 12100, 13000, 1),
               (9, "slot.fetch", 13100, 18000, 1),
               (10, "slot.join", 18100, 19800, 1)]
    program = [_ev(name, a, b - a, n=n, stage=s, boundary=0)
               for n, name, a, b, s in program]
    return {"device": device, "host": host, "program": program}


def test_handmade_idle_by_program_by_hand():
    ev = handmade()
    r = spans.reduce_program(ev)
    # idle gaps: 0-900, 7900-12500, 16000-16500, 17500-20500, 21000-24000
    # 0-900: slot.dispatch's own 700 (200-900) beats engine.decode's own
    #   100 and the cut decode's 50; 7900-12500: engine.decide's 1700
    #   beats slot.fetch 100, engine.decode's own 200, engine.admit's own
    #   200 (1400 less prefill 800, join 400), slot.prefill 800;
    # 16000-16500: inside slot.fetch; 17500-20500: slot.join 1700 beats
    #   slot.fetch 500 and engine.admit's own 200; 21000-24000: no phase
    #   of the program, so the driver's wait_arrival
    assert r["idle_by_program"] == pytest.approx({
        "slot.dispatch": 900e-9, "engine.decide": 4600e-9,
        "slot.fetch": 500e-9, "slot.join": 3000e-9,
        "wait_arrival": 3000e-9})
    s = tracing.reduce(ev)
    assert sum(r["idle_by_program"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    # the existing reduction still names the driver's phases
    assert set(s["idle_by_host"]) <= set(tracing.HOST_PHASES)
    assert [e["n"] for e in r["program_calls"]] == list(range(2, 11))


def test_handmade_host_gaps_by_hand():
    ev = handmade()
    s = tracing.reduce(ev)
    # engine.decode 100-9900: idle 100-900 and 7900-9900; the cut decode
    # is not counted
    assert spans.host_gap_ms(s, "engine.decode") == pytest.approx(2800e-6)
    # engine.admit 11100-19900: idle 11100-12500, 16000-16500, 17500-19900
    assert spans.host_gap_ms(s, "engine.admit") == pytest.approx(4300e-6)
    assert spans.host_gap_ms(s, "slot.fetch") == pytest.approx(
        (100 + 500 + 500) / 2 * 1e-6)
    # the held decode's executable starts 700 ns after its dispatch and
    # ends 100 ns before its fetch
    a = spans.decode_alignment(ev)
    assert a == {"s0": {"start_lag_ms": pytest.approx([7e-4] * 3),
                        "fetch_tail_ms": pytest.approx([1e-4] * 3)}}
    ev["program"] = []
    assert spans.host_gap_ms(tracing.reduce(ev), "engine.decode") is None


def test_prefill_pad_share_and_escalation_wait_by_hand():
    from repro.core.telemetry import Telemetry
    telem = Telemetry()
    ticks = iter([1.0, 2.0, 3.0, 4.0, 11.0, 12.0])
    clock = lambda: next(ticks)  # noqa: E731
    for tokens, padded in ((20, 32), (5, 8), (1, 64)):
        with telem.phase("slot.prefill", clock, 0, 0, tokens=tokens,
                         padded=padded):
            pass
    # the third ends at 12, outside [0, 10)
    assert spans.prefill_pad_share(telem.phases, 0.0, 10.0) == \
        pytest.approx(1 - 25 / 40)
    assert spans.prefill_pad_share(telem.phases, 20.0, 30.0) is None
    raw = telem.raw.append
    raw(("admit", 0.0, 1, 0, 0, ""))
    raw(("admit", 0.5, 2, 0, 0, ""))
    raw(("admit", 0.5, 3, 0, 0, ""))
    raw(("fire", 1.0, 0, (1, 2, 3)))
    raw(("escalate", 2.0, 2, 0))
    raw(("escalate", 3.0, 1, 0))
    raw(("fire", 3.5, 1, (2,)))
    raw(("fire", 4.25, 1, (1,)))
    raw(("close", 4.0, 3, "completed"))
    raw(("close", 5.0, 2, "completed"))
    raw(("close", 6.0, 1, "completed"))
    telem.finalize()
    assert spans.escalation_wait_ms(telem.spans, [1, 2, 3]) == \
        pytest.approx([1250.0, 1500.0])
    assert spans.escalation_wait_ms(telem.spans, [3, 9]) == []


def test_innermost_prefers_own_time_then_the_inner_phase():
    outer = _ev("engine.decode", 0, 100)
    inner = _ev("engine.decide", 40, 60)
    assert spans.innermost([outer, inner], 30, 100) == "engine.decide"
    assert spans.innermost([outer, inner], 0, 60) == "engine.decode"
    # a gap inside the child alone: both overlap it alike, the inner wins
    assert spans.innermost([outer, inner], 50, 60) == "engine.decide"
    assert spans.innermost([outer], 200, 300) is None


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "trace_v5e_cascade_chat_spans.json"
    return json.loads(path.read_text())


def test_recorded_slice_idle_by_program_sums_to_idle(recorded):
    ev = recorded["events"]
    s = tracing.reduce(ev)
    r = spans.reduce_program(ev)
    idle = s["window_s"] - s["busy_s"]
    assert abs(sum(r["idle_by_program"].values()) - idle) < 1e-6
    assert sum(s["idle_by_host"].values()) == pytest.approx(idle)
    for key in ("busy_s", "window_s", "idle_by_host", "idle_by_program"):
        got = s[key] if key in s else r[key]
        assert got == pytest.approx(recorded["expect"][key], rel=1e-9), key
    # the slice holds whole decode and admit calls of the program, and
    # the existing breakdown still names the driver's phases
    names = {e["name"] for e in r["program_calls"]}
    assert {"engine.decode", "engine.admit"} <= names
    assert set(s["idle_by_host"]) <= set(tracing.HOST_PHASES)


def test_recorded_slice_readers_by_hand(recorded):
    """The slice holds one qwen2 admit (one 193-token prompt in a 256
    bucket) and the fused decode after it. The device idles in eleven
    gaps inside the admit (1.130, 0.243, 2.176, 0.270, 4.365, 3.623,
    4.345, 3.141, 3.198, 3.419, 1.431 ms: the join path's eager updates)
    and in one of 3.028 ms inside the decode, after its executable."""
    from types import SimpleNamespace
    ev = recorded["events"]
    s = tracing.reduce(ev)
    assert spans.host_gap_ms(s, "engine.admit") == pytest.approx(
        27.347, abs=2e-3)
    assert spans.host_gap_ms(s, "engine.decode") == pytest.approx(
        3.028, abs=1e-3)
    phases = [SimpleNamespace(**r) for r in recorded["records"]]
    assert spans.prefill_pad_share(phases, 0.0, float("inf")) == \
        pytest.approx(1 - 193 / 256)
    a = spans.decode_alignment(ev)["s0"]
    # the executable shows on the device before the host dispatched it:
    # the device's clock runs ahead of the host's in this trace
    assert a["start_lag_ms"][0] < 0 < a["fetch_tail_ms"][0]


def test_recorded_slice_matches_the_in_memory_records(recorded):
    by_n = {r["n"]: r for r in recorded["records"]}
    program = recorded["events"]["program"]
    assert program and {e["n"] for e in program} == set(by_n)
    for e in program:
        r = by_n[e["n"]]
        assert (e["name"], e["stage"], e["boundary"]) == \
            (r["name"], r["stage"], r["boundary"])
        assert abs(e["dur_ns"] / 1e9 - (r["t1"] - r["t0"])) < 50e-6, e
    # children lie inside their parents, on both clocks
    starts = {e["n"]: (e["start_ns"], e["start_ns"] + e["dur_ns"])
              for e in program}
    for r in recorded["records"]:
        up = by_n.get(r["parent"])
        if up is not None:
            assert up["t0"] <= r["t0"] and r["t1"] <= up["t1"]
            a, b = starts[r["n"]]
            assert starts[up["n"]][0] <= a and b <= starts[up["n"]][1]


@pytest.fixture
def pallas_interpret(monkeypatch):
    import repro.kernels.top2gap as K

    def argmax_gap(scores):
        gap, idx = K.top2gap_pallas(scores, interpret=True)
        return idx, gap

    monkeypatch.setattr(K, "argmax_gap", argmax_gap)


def test_traced_tiny_run_holds_the_program_phases(pallas_interpret, quiet,
                                                  tmp_path):
    cell, cfgs = tiny_cell()
    out = spans.serve(cell, 2 ** 33 + 5, 2.0, True, True, quiet,
                      slice_path=str(tmp_path / "slice.json"),
                      model_configs=cfgs)
    assert out["failed"] == 0
    assert set(out["end_to_end"]) == {"ttft_p95_s", "itl_p95_ms", "setup_s"}
    assert 0 <= out["prefill_pad_share"] < 1
    assert out["escalations"] > 0 and out["escalation_wait_p50_ms"] >= 0
    assert out["program_calls"]["engine.decode"] > 0
    # the CPU has no device plane: all of the window is idle
    assert sum(out["idle_by_program"].values()) == pytest.approx(
        out["window_s"])
    cut = json.loads((tmp_path / "slice.json").read_text())
    by_n = {r["n"]: r for r in cut["records"]}
    assert cut["events"]["program"]
    for e in cut["events"]["program"]:
        r = by_n[e["n"]]
        assert (e["name"], e["stage"], e["boundary"]) == \
            (r["name"], r["stage"], r["boundary"])


def test_telemetry_off_serves_without_phases(pallas_interpret, quiet):
    cell, cfgs = tiny_cell()
    out = spans.serve(cell, 7, 1.0, False, False, quiet, model_configs=cfgs)
    assert set(out) == {"device", "failed", "end_to_end"}
    assert out["failed"] == 0
