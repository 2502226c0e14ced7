"""Find a cell's configuration, traffic mix, family, limits and per-layer
metric readers by the names ``BENCHMARK.json`` gives.

Everything that belongs to one configuration, mix or metric sits in a
file of its own under ``<root>/chipbench/``, so a later change adds a cell
by adding files and entries, never by editing the harness.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Catalog:
    def __init__(self, root: Path | str = ROOT):
        self.root = Path(root)
        self.dir = self.root / "chipbench"
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def limits(self, cell: str) -> dict:
        path = self.dir / "limits" / f"{cell}.json"
        return json.loads(path.read_text())["limits"] if path.exists() else {}

    def family(self, name: str):
        """A family is code: ``chipbench/families/<name>.py``."""
        return importlib.import_module(f"chipbench.families.{name}")

    def _applies(self, metric: dict, cell: str) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        moves = metric.get("moves")
        if moves is None:
            return True
        return any(m["name"] == moves and self._applies(m, cell)
                   for m in self.bench["end_to_end"])

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.bench["end_to_end"] if self._applies(m, cell)]

    def per_layer(self, cell: str) -> list[dict]:
        return [m for m in self.bench["per_layer"] if self._applies(m, cell)]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``metrics/<metric>.py``."""
        path = self.dir / "metrics" / f"{metric}.py"
        return _load_module(path, "chipbench_metric_" + metric.replace(".", "_")).read
