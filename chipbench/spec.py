"""Find the benchmark's parts by name.

Every part that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own under this directory, found by the
name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the deployment's sizes and layout;
- ``reference/<name>.py``: the plain reference a configuration names;
- ``traffic/<traffic>.json``: a traffic mix's parameters;
- ``arrivals/<kind>.py`` and ``lengths/<dist>.py``: arrival processes and
  length distributions that traffic files name;
- ``limits/<cell>.json``: the limits of the numbers ``correct`` compares;
- ``metrics/<metric>.py``: one per-layer metric reader;
- ``peaks.json``: the device peaks, keyed by ``device_kind``.

Adding any of them is adding a file and an entry; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

HERE = Path(__file__).resolve().parent


class UnknownDevice(KeyError):
    """The device kind has no row in ``peaks.json``."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "chipbench_part_" + "_".join(path.relative_to(path.parents[1]).with_suffix("").parts)
    name = name.replace("-", "_").replace(".", "_")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Parts:
    """The benchmark's files below one directory (this one, or a copy)."""

    def __init__(self, root: Path = HERE, benchmark_file: Path = None):
        self.root = Path(root)
        self.benchmark_file = benchmark_file or self.root.parent / "BENCHMARK.json"

    def benchmark(self) -> Dict[str, Any]:
        return load_json(self.benchmark_file)

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.benchmark()["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.benchmark_file}")

    def config(self, name: str) -> Dict[str, Any]:
        return load_json(self.root / "configs" / f"{name}.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return load_json(self.root / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> Dict[str, Any]:
        return load_json(self.root / "limits" / f"{cell}.json")

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        table = load_json(self.root / "peaks.json")["devices"]
        if device_kind not in table:
            raise UnknownDevice(
                f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
        return table[device_kind]

    def reference(self, name: str) -> ModuleType:
        return load_module(self.root / "reference" / f"{name}.py")

    def arrivals(self, kind: str) -> ModuleType:
        return load_module(self.root / "arrivals" / f"{kind}.py")

    def lengths(self, dist: str) -> ModuleType:
        return load_module(self.root / "lengths" / f"{dist}.py")

    def metric(self, name: str) -> ModuleType:
        return load_module(self.root / "metrics" / f"{name}.py")

    def per_layer_for(self, cell: str):
        """The per-layer metric entries a traced run of ``cell`` reports."""
        return [m for m in self.benchmark()["per_layer"]
                if cell in m.get("workloads", [cell])]

    def end_to_end_for(self, cell: str):
        return [m for m in self.benchmark()["end_to_end"]
                if cell in m.get("workloads", [cell])]
