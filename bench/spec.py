"""Find a cell, its configuration, its traffic mix, its metrics and its
plain reference by the names ``BENCHMARK.json`` gives them.

Layout, relative to the checkout's root:

* ``BENCHMARK.json``: the cells (``workloads``), configurations and metrics;
* the configuration's ``file`` (under ``bench/configs``): the deployment,
  with ``kind``, ``params``, ``reference`` and ``check``;
* ``bench/traffic/<traffic>.json``: the parameters of one traffic mix;
* ``bench/reference/<reference>.py``: the plain reference of a kind;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Dict, List

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path

    def reference(self) -> ModuleType:
        return load_module(self.root / "bench" / "reference"
                           / f"{self.config['reference']}.py")

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")


def load_module(path: pathlib.Path) -> ModuleType:
    """Import one file by its path (metric names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_file_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: pathlib.Path) -> Any:
    return json.loads(path.read_text())


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    try:
        w = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {name!r}; known: {known}") from None
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _read_json(root / entry["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    # A per-layer metric without ``workloads`` belongs to every cell that
    # reports the end-to-end metric it moves.
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)
