"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix. The configuration's file is
the one its ``BENCHMARK.json`` entry gives, and its graph generator is
``bench/generators/<generator>.py`` where it names one
(``harness.graphs``); a traffic mix is ``bench/traffic/<traffic>.json``;
every metric is read by ``bench/metrics/<metric name>.py``, whose
``read(run)`` returns a number or ``None`` when it finds nothing to read. A
new configuration, generator, mix or metric is new files and a new entry,
never an edit of the harness.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any

BENCH_DIR = Path(__file__).resolve().parent.parent


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    workloads: list[str] | None

    def reported_in(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]


def _metric(entry: dict) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"], workloads=entry.get("workloads"))


def load_manifest(root: Path) -> dict:
    path = Path(root) / "BENCHMARK.json"
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell named ``workload``, with its configuration and traffic files
    read and the metrics it reports."""
    root = Path(root)
    manifest = load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in map(_metric, manifest["end_to_end"]) if m.reported_in(workload)]
    layers = [m for m in map(_metric, manifest["per_layer"]) if m.reported_in(workload)]
    return Cell(
        name=workload,
        config_name=w["config"],
        traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=e2e,
        per_layer=layers,
    )


def load_module(directory: str, name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The module ``bench/<directory>/<name>.py``, loaded by path (a name
    may hold dots)."""
    path = Path(bench_dir) / directory / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{directory}_{name}", path)
    if spec is None or spec.loader is None or not path.is_file():
        raise FileNotFoundError(f"no {directory} module {name!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The reader of metric ``name``: ``bench/metrics/<name>.py``."""
    return load_module("metrics", name, bench_dir)


def read_metrics(metrics: list[Metric], run: Any, bench_dir: Path = BENCH_DIR) -> dict:
    """``{name: {"value", "unit"}}`` for every metric whose reader found
    something."""
    out = {}
    for m in metrics:
        value = metric_reader(m.name, bench_dir).read(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
