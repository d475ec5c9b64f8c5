"""What `BENCHMARK.json` names, found by name: a cell's configuration file,
its traffic mix (`chipbench/traffic/<mix>.json`), its metrics, and each
per-layer metric's reader (`chipbench/metrics/<metric>.py`).

Everything is looked up under `root`, the checkout holding BENCHMARK.json,
so a cell, configuration, mix or metric is added by adding files and
entries; no code here names one.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]


class Spec:
    def __init__(self, root: str | pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())

    def _one(self, key: str, name: str) -> dict:
        hits = [e for e in self.bench[key] if e["name"] == name]
        if len(hits) != 1:
            raise KeyError(f"{key}: no single entry named {name!r}")
        return hits[0]

    def workload(self, name: str) -> dict:
        return self._one("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._one("configs", name)["file"])
                          .read_text())

    def traffic(self, name: str) -> dict:
        path = self.root / "chipbench" / "traffic" / f"{name}.json"
        return json.loads(path.read_text())

    def metrics(self, kind: str, workload: str) -> list[dict]:
        """The `end_to_end` or `per_layer` metrics a cell reports: those
        without a `workloads` key, and those that list the cell."""
        return [m for m in self.bench[kind]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        """The `read(window)` function of a per-layer metric."""
        path = self.root / "chipbench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
