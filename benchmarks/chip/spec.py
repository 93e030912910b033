"""The benchmark's data, found by name.

``BENCHMARK.json`` at the checkout root lists configurations, traffic mixes
(cells) and metrics. Whatever belongs to one of them sits in a file of its
own, which this module finds by the name alone:

* a configuration: the ``file`` its ``configs`` entry names;
* a traffic mix: ``traffic/<mix>.json`` beside this module;
* a per-layer metric: ``metrics/<metric>.py``, whose ``read(run)`` returns
  the number or None.

Adding a configuration, mix or metric is adding files and entries; nothing
here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


@dataclass
class Cell:
    name: str
    config_name: str
    config: dict            # the configuration's file
    traffic_name: str
    traffic: dict           # the mix's file
    chips: int
    end_to_end: List[dict]  # metrics this cell reports with --trace 0
    per_layer: List[dict]   # metrics this cell reports with --trace 1
    root: Path = REPO
    readers: Dict[str, Callable] = field(default_factory=dict)


def chip_dir(root: Path) -> Path:
    return Path(root) / "benchmarks" / "chip"


def load_benchmark(root: Path = REPO) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(name: str, root: Path = REPO) -> Callable:
    path = chip_dir(root) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, root: Path = REPO,
              bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((Path(root) / conf["file"]).read_text())
    traffic = json.loads(
        (chip_dir(root) / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in e2e_names and reports(m, name)]
    cell = Cell(name, w["config"], config, w["traffic"], traffic,
                int(w["chips"]), e2e, per_layer, Path(root))
    cell.readers = {m["name"]: load_reader(m["name"], root)
                    for m in per_layer}
    return cell
