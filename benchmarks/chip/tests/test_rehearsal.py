"""A run of each cell through ``run_cell`` on the CPU at a tiny size, with
the Pallas top-2-gap kernel in interpret mode inside the compiled steps:
the result line has its keys in order, and the comparison passes."""
import json

import pytest

import harness
import spec
from conftest import tiny_cell

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.fixture
def pallas_interpret(monkeypatch):
    import repro.kernels.top2gap as K

    def argmax_gap(scores):
        gap, idx = K.top2gap_pallas(scores, interpret=True)
        return idx, gap

    monkeypatch.setattr(K, "argmax_gap", argmax_gap)


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_line(name, pallas_interpret, quiet):
    cell, cfgs = tiny_cell(name)
    out = harness.run_cell(cell, 2 ** 33 + 17, 1.5, model_configs=cfgs,
                           say=quiet)
    line = json.loads(json.dumps(harness.result_line(out)))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True, line["checks"]
    assert out["compiles_in_window"] == 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_traced_rehearsal_reports_per_layer(quiet):
    cell, cfgs = tiny_cell()
    out = harness.run_cell(cell, 99, 2.0, trace=True, model_configs=cfgs,
                           say=quiet, peak=harness.peak_of("TPU v5 lite"))
    line = harness.result_line(out)
    assert list(line)[-2:] == ["breakdown", "checks"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    # the CPU has no device plane: the device's metrics stay silent and
    # the host's are read
    assert "queue_wait_p50_ms" in line["metrics"]
    assert "decode_call_ms" in line["metrics"]
    # the program's telemetry and its phases in the trace reach the readers
    for name in ("escalation_wait_p50_ms", "prefill_pad_share",
                 "join_host_gap_ms.chat", "decode_host_gap_ms.chat"):
        assert name in line["metrics"], name
    assert set(line["metrics"]) <= {m["name"] for m in cell.per_layer}
