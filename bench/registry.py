"""Finds every part of a cell by its name in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix; a
metric is named in ``end_to_end`` or ``per_layer``.  Each part is a file of
its own, found by name, so a new cell, configuration, traffic mix or
per-layer metric is added by adding files and entries, never by editing
the harness:

* configuration ``<c>``: the ``file`` of its ``configs`` entry (JSON);
* traffic mix ``<t>``:   ``bench/traffic/<t>.json``;
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, exposing
  ``read(run) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from . import traffic as traffic_mod

BENCH_DIR = "bench"


@dataclasses.dataclass
class Cell:
    root: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list      # metric entries this cell reports with --trace 0
    per_layer: list       # ... and with --trace 1


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: str, name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    wl = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[wl["config"]]["file"])) as f:
        config = json.load(f)
    mix = traffic_mod.load(traffic_path(root, wl["traffic"]))
    return Cell(root, wl, config, mix,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def traffic_path(root: str, name: str) -> str:
    return os.path.join(root, BENCH_DIR, "traffic", f"{name}.json")


def metric_path(root: str, name: str) -> str:
    return os.path.join(root, BENCH_DIR, "metrics", f"{name}.py")


def metric_reader(root: str, name: str):
    """``read`` of the per-layer metric ``name``'s own file."""
    path = metric_path(root, name)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
