"""The cell a run measures, resolved by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a file of its own
(``bench/configs/<config>.json`` via the configuration's ``file`` entry,
``bench/traffic/<traffic>.json``), its correctness limits are
``bench/limits/<cell>.json``, and each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds
files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list      # metric entries that this cell reports


def _load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark file {path}")
    return json.loads(path.read_text())


def load(root: pathlib.Path, workload: str) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(root / "bench" / "limits" / f"{workload}.json")
    reports = lambda m: workload in m.get("workloads", [workload])
    return Cell(name=workload, chips=int(w["chips"]), config=cfg,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"] if reports(m)],
                per_layer=[m for m in bench["per_layer"] if reports(m)])


def reader(root: pathlib.Path, metric: str):
    """The ``read(run)`` function of a per-layer metric."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
