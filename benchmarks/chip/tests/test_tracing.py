"""The reduction from trace to metrics, on small traces kept in
``tests/data``: one made by hand and counted by hand, and one recorded on
a v5e chip (a slice of a traced cascade-chat run)."""
import json
from pathlib import Path

import pytest

import tracing

DATA = Path(__file__).resolve().parent / "data"


def test_handmade_trace_by_hand():
    s = tracing.reduce(json.loads((DATA / "trace_handmade.json")
                                  .read_text()))
    # window: host phases span 500..10000 ns
    assert s["window_s"] == pytest.approx(9500e-9)
    # busy: [1000, 2000] u [2500, 3500] u [6000, 8000] u [9500, 10000]
    # (fusion.2 lies inside the kernel's interval; fusion.4 is clipped)
    assert s["busy_s"] == pytest.approx(4500e-9)
    # idle gaps, each to the host phase overlapping it most:
    # 500-1000 and 2000-2500 decode.s0; 3500-6000 overlaps decode.s0 by
    # 600, bookkeeping by 1500, admit.s1 by 400: bookkeeping;
    # 8000-9500 overlaps admit.s1 by 100, wait_arrival by 1400
    assert s["idle_by_host"] == pytest.approx({
        "decode.s0": 1000e-9, "bookkeeping": 2500e-9,
        "wait_arrival": 1500e-9})
    assert sum(s["idle_by_host"].values()) + s["busy_s"] == \
        pytest.approx(s["window_s"])
    # executables: their own events; operations: self time (the kernel
    # less fusion.2 nested in it; fusion.4 clipped), under the executable
    # that encloses them
    assert s["by_module"] == pytest.approx({
        "jit_fused_decode": 3000e-9, "jit_bucketed_prefill": 2000e-9})
    assert s["by_op"] == pytest.approx({
        "jit_fused_decode/fusion.1": 1000e-9,
        "jit_fused_decode/top2gap_kernel": 500e-9,
        "jit_fused_decode/fusion.2": 500e-9,
        "jit_bucketed_prefill/fusion.3": 2000e-9, "/fusion.4": 500e-9})
    assert s["calls_by_module"] == {"jit_fused_decode": 1,
                                    "jit_bucketed_prefill": 1}
    assert tracing.kernel_seconds(s, ["top2gap"]) == (pytest.approx(1e-6), 1)
    # the engine calls tagged with their boundary, and the device time
    # that starts inside each
    assert [(h["name"], h["boundary"]) for h in s["host_calls"]] == [
        ("decode.s0", 0), ("admit.s1", 0)]
    assert tracing.module_seconds(s, "fused_decode", (500, 4100)) == \
        pytest.approx(3e-6)
    assert tracing.module_seconds(s, "fused_decode", (5600, 8100)) == 0
    assert tracing.kernel_seconds(s, ["top2gap"], (5600, 8100)) == (0, 0)
    b = tracing.breakdown(s, n=2)
    assert b["device_ops"][0] == ["jit_bucketed_prefill/fusion.3",
                                  pytest.approx(2e-6)]
    assert [k for k, _ in b["idle_gaps"]] == ["bookkeeping", "wait_arrival"]


def test_union_and_gaps():
    assert tracing.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4),
                                                                (5, 6)]
    assert tracing.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tracing.clip([(0, 3), (5, 9)], 1, 6) == [(1, 3), (5, 6)]
    assert tracing.short("%copy.7 = bf16[24,16]{2,1:T(8)} copy(%p)") == \
        "%copy.7 = bf16[24,16]"


def test_recorded_chip_trace():
    rec = json.loads((DATA / "trace_v5e_cascade_chat.json").read_text())
    s = tracing.reduce(rec["events"])
    for key, want in rec["expect"].items():
        assert s[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < s["busy_s"] <= s["window_s"]
    assert sum(s["idle_by_host"].values()) + s["busy_s"] == \
        pytest.approx(s["window_s"])
    # the ops' self times never exceed the busy time they are part of
    assert sum(s["by_op"].values()) >= s["busy_s"] * (1 - 1e-9)
    assert all(v >= 0 for v in s["by_op"].values())
