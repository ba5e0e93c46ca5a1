"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix; the
configuration's file is in its ``configs`` entry, the mix is
``bench/traffic/<traffic>.json``, the cell's correctness limits are
``bench/cells/<cell>.json`` and each metric's reader is
``bench/metrics/<metric>.py``. The configuration file names, beside its
plain reference (``"reference"``: ``bench/references/<reference>.py``),
its model module (``"model"``: ``bench/models/<model>.py``), which holds
all that the benchmark knows of the model's blocks. Adding a
configuration, a model kind, a cell or a metric adds files and entries
and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, List

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
MODELS = BENCH / "models"


class Spec:
    def __init__(self, path: Path = ROOT / "BENCHMARK.json",
                 bench_dir: Path = BENCH):
        self.path = Path(path)
        self.root = self.path.parent
        self.dir = Path(bench_dir)
        self.data = json.loads(self.path.read_text())

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in {self.path}")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def limits(self, cell: str) -> dict:
        path = self.dir / "cells" / f"{cell}.json"
        return json.loads(path.read_text())["limits"] if path.exists() else {}

    def end_to_end(self, cell: str) -> List[dict]:
        return [m for m in self.data["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[dict]:
        moved = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.data["per_layer"]
                if cell in m.get("workloads", [])
                or ("workloads" not in m and m["moves"] in moved)]

    def reader(self, metric: str) -> Callable:
        path = BENCH / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no reader for metric {metric!r}: {path}")
        return _load(path, "bench_metric_" + metric).read

    def model(self, conf: dict) -> ModuleType:
        """The model module that the configuration file names with
        ``"model"``, loaded from ``MODELS`` by path (its interface is in
        ``bench/models/__init__.py``)."""
        name = conf.get("model")
        path = MODELS / f"{name}.py"
        if not name or not path.is_file():
            raise FileNotFoundError(
                f"configuration {conf.get('name')!r} names model {name!r}: "
                f"no module {path}")
        return _load(path, "bench_model_" + name)


def _load(path: Path, name: str) -> ModuleType:
    mod_name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs, as an import would: a dataclass in the
    # module looks its module up there
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
