"""The per-layer readers on a run built by hand: stamps, calls and the
hand-made trace, with their values counted from the same inputs."""
import json
from pathlib import Path

import pytest

import costs
import harness
import tracing
from driver import Call, Record
from spec import load_cell, load_family, load_reader
from traffic import Arrival

DATA = Path(__file__).resolve().parent / "data"
F = load_family("dense_gqa")
A = F.Arch(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
           d_ff=16, vocab=32000, tied=False, qkv_bias=False,
           rope_theta=1e4, eps=1e-5, window=0)
PEAK = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e10}


def run_by_hand():
    import numpy as np
    cell = load_cell("cascade-chat")
    trace = tracing.reduce(json.loads((DATA / "trace_handmade.json")
                                      .read_text()))
    calls = [Call("admit", 0, 0.0, 0.5, 2, prompt_lens=[4, 6],
                  padded_rows=2, boundary=0),
             Call("decode", 0, 0.5, 1.0, 2, padded_rows=4, depths=[4, 6],
                  boundary=0),
             Call("admit", 1, 1.0, 1.5, 1, prompt_lens=[4], padded_rows=1,
                  boundary=0),
             Call("decode", 1, 1.5, 2.0, 1, padded_rows=4, depths=[4],
                  boundary=0)]
    rec = Record(Arrival(0, 0.0, np.zeros(4, np.int32), 2), due=0.0)
    rec.stamps = {0: [0.5, 1.0], 1: [1.5, 2.0]}
    rec.admit_start = {0: 0.0, 1: 1.0}
    return harness.RunData(cell, 0.0, 2.0, 2.0, [rec], calls,
                           ["qwen2-0.5b", "h2o-danube-1.8b"], 4, [A, A],
                           [F, F], PEAK, trace=trace)


def test_host_readers():
    run = run_by_hand()
    read = lambda n: load_reader(n)(run)  # noqa: E731
    assert read("reprefill_share") == pytest.approx(4 / 14)
    assert read("queue_wait_p50_ms") == 0.0
    assert read("prefill_time_share.chat") == pytest.approx(0.5)
    assert read("decode_call_ms") == pytest.approx(500.0)


def test_trace_readers():
    run = run_by_hand()
    read = lambda n: load_reader(n)(run)  # noqa: E731
    # the trace holds two of the four calls whole (decode.s0 and admit.s1
    # of boundary 0); the others are left out on both sides
    assert [(c.kind, c.stage) for c, _ in run.traced_calls()] == [
        ("decode", 0), ("admit", 1)]
    # the fused decode's 3000 ns, inside decode.s0, against the ideal of
    # that call alone
    ideal = costs.ideal_seconds(*F.decode_call(A, run.calls[1]), PEAK)
    assert read("decode_hbm_roofline.chat") == pytest.approx(
        100 * ideal / 3000e-9)
    flops = F.prefill_flops(A, 4) + F.token_flops(A, 5) \
        + F.token_flops(A, 7)
    assert read("mfu.chat") == pytest.approx(
        100 * flops / (9500e-9 * PEAK["bf16_flops"]))
    # the two calls' logits against the kernel event's 1000 ns in decode.s0
    nbytes = sum(costs.top2gap_bytes(r, A.vocab) for r in (4, 1))
    assert read("top2gap_roofline.chat") == pytest.approx(
        100 * nbytes / PEAK["hbm_bytes_per_s"] / 1000e-9)
    assert read("device_idle_share.chat") == pytest.approx(1 - 4500 / 9500)


def test_program_readers_by_hand():
    """The readers of the program's telemetry: escalation waits of the
    window's requests, and the pad share of the window's prefills; silent
    where the run had no telemetry (``--trace 0``)."""
    from repro.core.telemetry import Telemetry
    run = run_by_hand()
    read = lambda n: load_reader(n)(run)  # noqa: E731
    for name in ("escalation_wait_p50_ms", "prefill_pad_share"):
        assert read(name) is None
    telem = Telemetry()
    ticks = iter([0.1, 0.2, 0.3, 0.4, 2.5, 2.6])
    clock = lambda: next(ticks)  # noqa: E731
    for tokens, padded in ((20, 32), (5, 8), (1, 64)):
        with telem.phase("slot.prefill", clock, 0, 0, tokens=tokens,
                         padded=padded):
            pass
    raw = telem.raw.append
    raw(("admit", 0.0, 0, 0, 0, ""))
    raw(("fire", 0.0, 0, (0,)))
    raw(("escalate", 0.5, 0, 0))
    raw(("fire", 0.75, 1, (0,)))
    raw(("close", 2.0, 0, "completed"))
    run.telemetry = telem.finalize()
    # the third prefill ends at 2.6, after the window's close at 2.0
    assert read("prefill_pad_share") == pytest.approx(1 - 25 / 40)
    assert read("escalation_wait_p50_ms") == pytest.approx(250.0)
    run.records[0].due = 5.0      # the request is no longer the window's
    assert read("escalation_wait_p50_ms") is None
