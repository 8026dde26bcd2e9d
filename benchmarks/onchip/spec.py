"""Everything a run needs, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration's entry
names its file; the mix is ``traffic/<traffic>.json``; the configuration's
``model_type`` picks ``adapters/<model_type>.py`` (the program side) and
``references/<model_type>.py`` (the plain reference); each metric is read
by ``metrics/<metric name>.py``. A later cell, mix, configuration or
metric is a new file and a new entry, with no edit to any file here.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    base: str             # the benchmark's directory
    name: str
    chips: int
    config: Dict          # the configuration file's contents
    traffic: Dict         # the mix file's contents
    end_to_end: List[Dict]
    per_layer: List[Dict]

    def adapter(self):
        return load_module(os.path.join(
            self.base, "adapters", f"{self.config['model_type']}.py"),
            f"adapter_{self.config['model_type']}")

    def reference(self):
        return load_module(os.path.join(
            self.base, "references", f"{self.config['model_type']}.py"),
            f"reference_{self.config['model_type']}")

    def reader(self, metric: str) -> Callable:
        return load_module(os.path.join(self.base, "metrics", f"{metric}.py"),
                           f"metric_{metric}").read


def reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: str = "", base: str = HERE) -> Cell:
    """The cell ``name`` of ``bench_path`` (default: the repository's
    ``BENCHMARK.json``), its files looked up under ``base``."""
    path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    root = os.path.dirname(os.path.abspath(path))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {path}; it has "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(base, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return Cell(
        base=base, name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if reports(m, name)])
