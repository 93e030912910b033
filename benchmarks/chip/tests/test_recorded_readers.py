"""The per-layer readers on the two traces recorded on a v5e chip
(``data/trace_v5e_cascade_chat*.json``), with engine calls laid on the
trace's driver phases (their prompt lengths and depths made up, the same
each time) and the cell's own architectures. The expected values were read
by the readers as they stood before the architecture families, and by
``spans`` for the program's phases; the first trace's phases carry no
boundary, so no call is matched to its device time and the rooflines and
``mfu.chat`` stay silent there."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import tracing
from driver import Call, Record
from spec import load_cell, load_reader
from traffic import Arrival

DATA = Path(__file__).resolve().parent / "data"
CHAT = "trace_v5e_cascade_chat.json"
SPANS = "trace_v5e_cascade_chat_spans.json"

EXPECTED = {
    CHAT: {
        "reprefill_share": 0.0,
        "queue_wait_p50_ms": 6.000000000000005,
        "prefill_time_share.chat": 0.5575155389230625,
        "decode_call_ms": 13.675179999999898,
        "decode_hbm_roofline.chat": None,
        "mfu.chat": None,
        "top2gap_roofline.chat": None,
        "device_idle_share.chat": 0.43902217533682597,
        "escalation_wait_p50_ms": None,
        "prefill_pad_share": None,
        "join_host_gap_ms.chat": None,
        "decode_host_gap_ms.chat": None,
    },
    SPANS: {
        "reprefill_share": 0.0,
        "queue_wait_p50_ms": 3.0000000000000027,
        "prefill_time_share.chat": 0.5608395487948497,
        "decode_call_ms": 14.365767000000002,
        "decode_hbm_roofline.chat": 2.33713696862193,
        "mfu.chat": 14.136872142687332,
        "top2gap_roofline.chat": 15.535640778848359,
        "device_idle_share.chat": 0.6655411582511823,
        # no escalation in the slice
        "escalation_wait_p50_ms": None,
        # one 193-token prompt in a 256 bucket
        "prefill_pad_share": 0.24609375,
        "join_host_gap_ms.chat": 27.347379,
        "decode_host_gap_ms.chat": 3.028193,
    },
}


def recorded_run(name):
    rec = json.loads((DATA / name).read_text())
    ev = rec["events"]
    host = sorted(ev["host"], key=lambda h: h["start_ns"])
    calls, records = [], []
    for i, h in enumerate(host):
        kind, _, stage = h["name"].partition(".s")
        if kind not in ("admit", "decode"):
            continue
        t0 = h["start_ns"] / 1e9
        t1 = t0 + h["dur_ns"] / 1e9
        b = -1 if h.get("boundary") is None else int(h["boundary"])
        if kind == "admit":
            lens = [193, 17 + 31 * i][:1 + i % 2]
            calls.append(Call("admit", int(stage), t0, t1, len(lens),
                              prompt_lens=lens, padded_rows=len(lens),
                              boundary=b))
            r = Record(Arrival(i, 0.0, np.zeros(4, np.int32), 2),
                       due=t0 - 0.003 * (1 + i % 3))
            r.admit_start = {int(stage): t0}
            records.append(r)
        else:
            calls.append(Call("decode", int(stage), t0, t1, 12,
                              padded_rows=16,
                              depths=[(97 * i + 131 * r) % 2000
                                      for r in range(12)], boundary=b))
    win0 = host[0]["start_ns"] / 1e9 - 0.01
    win1 = max(h["start_ns"] + h["dur_ns"] for h in host) / 1e9
    cell = load_cell("cascade-chat")
    names = cell.config["stages"]
    fams = [cell.family(n) for n in names]
    archs = [f.arch(cell.config["models"][n]) for f, n in zip(fams, names)]
    telemetry = None
    if "program" in ev:
        # the program's phases on the trace's clock, with the counts of
        # their in-memory records
        by_n = {r["n"]: r for r in rec["records"]}
        telemetry = SimpleNamespace(spans={}, phases=[SimpleNamespace(
            name=e["name"], t0=e["start_ns"] / 1e9,
            t1=(e["start_ns"] + e["dur_ns"]) / 1e9,
            counts=by_n[e["n"]]["counts"]) for e in ev["program"]])
    return harness.RunData(cell, win0, win1, win1 - win0, records, calls,
                           names, 16, archs, fams,
                           harness.peak_of("TPU v5 lite"),
                           trace=tracing.reduce(ev), telemetry=telemetry)


@pytest.fixture(scope="module")
def runs():
    return {name: recorded_run(name) for name in EXPECTED}


@pytest.mark.parametrize("trace,metric", [
    (t, m) for t in EXPECTED for m in EXPECTED[t]])
def test_reader_reads_as_before(runs, trace, metric):
    want = EXPECTED[trace][metric]
    got = load_reader(metric)(runs[trace])
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12, abs=1e-12)


def test_expected_readers_are_the_benchmarks():
    from spec import load_benchmark
    names = {m["name"] for m in load_benchmark()["per_layer"]}
    for want in EXPECTED.values():
        assert set(want) <= names
