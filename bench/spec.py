"""Find a cell's data by the names in BENCHMARK.json.

A cell names a configuration (`configs/<name>.json`, via the `file` of
its entry) and a traffic mix (`traffic/<name>.json`); each per-layer
metric is read by `layers/<metric name>.py`. Nothing here knows any
cell, configuration, mix or metric by name, so a later change adds a
cell, a mix or a metric by adding files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Cell:
    """One workload of BENCHMARK.json with its configuration, traffic
    mix and metric entries loaded."""

    def __init__(self, name: str, root: Path = ROOT):
        path = root / "BENCHMARK.json"
        if not path.is_file():
            raise FileNotFoundError(f"no BENCHMARK.json at {root}")
        self.bench = json.loads(path.read_text())
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; known: "
                           f"{sorted(cells)}")
        self.name = name
        self.workload = cells[name]
        cfgs = {c["name"]: c for c in self.bench["configs"]}
        entry = cfgs[self.workload["config"]]
        self.config = json.loads((root / entry["file"]).read_text())
        self.traffic = json.loads(
            (BENCH / "traffic" / f"{self.workload['traffic']}.json")
            .read_text())
        self.chips = int(self.workload["chips"])

    def _metrics(self, kind: str) -> list:
        return [m for m in self.bench[kind]
                if name_in(self.name, m.get("workloads"))]

    @property
    def end_to_end(self) -> list:
        return self._metrics("end_to_end")

    @property
    def per_layer(self) -> list:
        return self._metrics("per_layer")


def name_in(cell: str, workloads) -> bool:
    return workloads is None or cell in workloads


def layer_reader(metric: str):
    """The `read(ctx)` function of `layers/<metric>.py`."""
    path = BENCH / "layers" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
