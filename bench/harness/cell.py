"""A cell of ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

* ``bench/configs/<config>.json`` -- the deployment (the workload entry
  names it through its configuration's ``file``);
* ``bench/traffic/<traffic>.json`` -- the mix's parameters, with
  ``"driver"`` naming the general generator in ``bench/drivers/``;
* ``bench/reference/<reference>.py`` -- the plain reference the
  configuration names under ``"reference"``;
* ``bench/metrics/<metric>.py`` -- one reader per per-layer metric.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def driver(self):
        return importlib.import_module(f"bench.drivers.{self.traffic['driver']}")

    def reference(self):
        return importlib.import_module(f"bench.reference.{self.config['reference']}")

    def readers(self) -> Dict[str, Callable]:
        out = {}
        for m in self.per_layer:
            path = BENCH / "metrics" / f"{m['name']}.py"
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"),
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            out[m["name"]] = mod.read
        return out


def find_cell(bench: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / cfg["file"]) as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)
