"""The benchmark is driven by data: a configuration, a traffic mix and a
per-layer metric added as new files and new entries of BENCHMARK.json are
found by name, and every entry that is there has its files."""
import json
import shutil
from pathlib import Path

import pytest

import spec

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]


def test_added_files_are_found_by_name(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    chip = tmp_path / "benchmarks/chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads(
        (chip / "configs/cascade-qwen2-danube.json").read_text())
    conf["name"], conf["n_slots"] = "cascade-8slot", 8
    (chip / "configs/cascade-8slot.json").write_text(json.dumps(conf))
    mix = json.loads((chip / "traffic/chat-cascade.json").read_text())
    mix["rate_rps"] = 1.5
    (chip / "traffic/chat-slow.json").write_text(json.dumps(mix))
    (chip / "metrics/boundaries_per_s.py").write_text(
        "def read(run):\n"
        "    return len(run.window_calls('decode')) / run.seconds\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "cascade-8slot", "source": "https://example.org/c",
        "file": "benchmarks/chip/configs/cascade-8slot.json",
        "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "cascade-slow", "config": "cascade-8slot",
        "traffic": "chat-slow", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "boundaries_per_s", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "admission",
        "moves": "itl_p95_ms", "workloads": ["cascade-slow"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_s", "itl_p95_ms"):
            m["workloads"].append("cascade-slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("cascade-slow", root=tmp_path)
    assert cell.config["n_slots"] == 8
    assert cell.traffic["rate_rps"] == 1.5
    assert "boundaries_per_s" in cell.readers
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p95_s", "itl_p95_ms", "setup_s"}

    class Run:
        seconds = 2.0

        def window_calls(self, kind):
            return [kind] * 6

    assert cell.readers["boundaries_per_s"](Run()) == 3.0
    # the cells that were there load as before
    assert spec.load_cell("cascade-chat", root=tmp_path).config[
        "n_slots"] == 16


BENCH = spec.load_benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_complete(cell):
    c = spec.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "a cell reports at least one per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in e2e
    for s in c.config["stages"]:
        assert s in c.config["models"] and s in c.config["limits"]


def test_every_metric_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        for w in m.get("workloads", []):
            assert spec.reports(e2e[m["moves"]], w), (m["name"], w)
        assert (CHIP / "metrics" / f"{m['name']}.py").exists()
