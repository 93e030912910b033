"""The benchmark's data, found by name.

``BENCHMARK.json`` at the checkout root lists configurations, traffic mixes
(cells) and metrics. Whatever belongs to one of them sits in a file of its
own, which this module finds by the name alone:

* a configuration: the ``file`` its ``configs`` entry names;
* a traffic mix: ``traffic/<mix>.json`` beside this module;
* a per-layer metric: ``metrics/<metric>.py``, whose ``read(run)`` returns
  the number or None;
* a model's architecture family: ``families/<family>.py``, named by the
  ``family`` key of each ``models`` entry of the configuration's file.

Adding a configuration, mix, metric or family is adding files and entries;
nothing here changes. A configuration of an architecture the benchmark
already serves names an existing family. One of a new architecture brings
its family module, which provides:

* ``arch(entry)``: its own shape object from the ``models`` entry (HF key
  names), exposing at least ``vocab`` and ``eps``, hashable (the reference
  jits with it as a static argument);
* ``stated(arch)`` and ``served(cfg)``: the shapes the file states and the
  ones the program's config serves, as equal tuples when they agree;
* ``decode_call(arch, call)`` -> ``(flops, bytes)`` and
  ``call_flops(arch, call)``: counts of what a ``driver.Call`` requires,
  from its depths, prompt lengths, rows and ``counts`` (the engine's
  per-call stat deltas, such as data-dependent reads);
* the plain float32 reference at ``Precision.HIGHEST``, importing nothing
  of ``src/``: ``hidden(arch, params, tokens, quant=None)``, the final
  hidden states of ``tokens`` (padded to any length), and
  ``stats(arch, params, x, target, quant=None)``, per position of ``x``
  the best logit less that of ``target``, the top-1 minus top-2 gap and
  the argmax; ``quant`` (``int8`` or ``fp8``) is the precision control.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
import zlib
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

    def family(self, model: str):
        """The family module of stage model ``model``."""
        return load_family(self.config["models"][model].get("family"),
                           self.root)


def chip_dir(root: Path) -> Path:
    return Path(root) / "benchmarks" / "chip"


def load_benchmark(root: Path = REPO) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The module in the file ``path``, loaded once per process under a
    name made from its path (so that the same name in two checkouts is two
    modules)."""
    name = "chip_{}_{:08x}".format(
        "".join(ch if ch.isalnum() else "_" for ch in path.stem),
        zlib.crc32(str(path).encode()))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = REPO) -> Callable:
    return load_module(
        (chip_dir(root) / "metrics" / f"{name}.py").resolve()).read


def load_family(name: Optional[str], root: Path = REPO):
    """``families/<name>.py``; a missing or unknown name is an error that
    lists the known families (there is no default)."""
    here = chip_dir(root) / "families"
    known = sorted(p.stem for p in here.glob("*.py"))
    if name not in known:
        raise KeyError(f"a models entry names family {name!r}; every entry "
                       f"names one of {known}")
    return load_module((here / f"{name}.py").resolve())


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
    for model in config["models"]:
        cell.family(model)
    return cell
