"""Finds a cell's configuration, traffic mix, cell settings and per-layer
metric readers by the names ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own under the benchmark directory:

    configs/<config>.json    model sizes, serving settings, weight recipe
    traffic/<mix>.json       parameters of the one general generator
    cells/<workload>.json    the cell's arrival rate and correctness limits
    metrics/<metric>.py      a reader: ``read(run) -> float | None``

so a new cell, mix or metric is added by adding files, never by editing one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class Spec:
    """One benchmark directory plus the ``BENCHMARK.json`` that names it."""

    def __init__(self, benchmark: dict, bench_dir: Path = BENCH_DIR):
        self.benchmark = benchmark
        self.dir = Path(bench_dir)

    @classmethod
    def load(cls, root: Path = ROOT, bench_dir: Path = BENCH_DIR) -> "Spec":
        with open(Path(root) / "BENCHMARK.json") as f:
            return cls(json.load(f), bench_dir)

    def workload(self, name: str) -> dict:
        for w in self.benchmark["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.benchmark['workloads']]}")

    def _json(self, kind: str, name: str) -> dict:
        path = self.dir / kind / f"{name}.json"
        with open(path) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def cell(self, name: str) -> dict:
        return self._json("cells", name)

    def metrics(self, kind: str, workload: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` entries that this cell
        reports: those without a ``workloads`` key, and those that list it."""
        return [m for m in self.benchmark[kind]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str) -> ModuleType:
        """The module ``metrics/<metric>.py``, loaded by path (metric names
        may hold dots)."""
        path = self.dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def readers(self, workload: str) -> Dict[str, ModuleType]:
        return {m["name"]: self.reader(m["name"])
                for m in self.metrics("per_layer", workload)}
