"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, configuration, traffic mix or metric by name:
each is a file under ``bench/`` whose path follows from its name.

* ``bench/configs/<config>.json``     model sizes (+ ``bench/configs/<model>.py``,
  the plain reference and work counts of the model it names)
* ``bench/traffic/<traffic>.json``    traffic parameters (+ the loop it names,
  ``bench/loops/<loop>.py``)
* ``bench/metrics/<metric>.py``       one reader per metric
* ``bench/limits/<cell>.json``        the limits that decide ``correct``
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here; the message says why."""


def load_json(path: pathlib.Path) -> Dict:
    """Parse a benchmark file; a missing one is a :class:`BenchError`."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    """Import a Python file by path (names may hold '.' and '-')."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """``BENCHMARK.json`` and the files its names lead to, under ``root``."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.bench = self.root / "bench"
        self.data = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> Dict:
        """The ``workloads`` entry of a cell."""
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> Dict:
        """A configuration's file, by the ``configs`` entry's ``file``."""
        for c in self.data["configs"]:
            if c["name"] == name:
                return load_json(self.root / c["file"])
        raise BenchError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        """A traffic mix's parameters."""
        return load_json(self.bench / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> Dict:
        """The limits that decide a cell's ``correct``."""
        return load_json(self.bench / "limits" / f"{cell}.json")

    def model(self, name: str):
        """A model's plain reference and work counts."""
        return load_module(self.bench / "configs" / f"{name}.py")

    def loop(self, name: str):
        """A measurement loop (set-up, window, check)."""
        return load_module(self.bench / "loops" / f"{name}.py")

    def reader(self, metric: str):
        """A metric's reader."""
        return load_module(self.bench / "metrics" / f"{metric}.py")

    def metrics_of(self, cell: str, per_layer: bool) -> List[Dict]:
        """The cell's end-to-end metrics (``per_layer=False``) or per-layer
        metrics: those that list the cell, or list no cells at all."""
        group = self.data["per_layer" if per_layer else "end_to_end"]
        return [m for m in group
                if cell in m.get("workloads", [cell])]

