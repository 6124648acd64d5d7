"""Finds a cell's files by the names in BENCHMARK.json.

A cell is one entry of ``workloads``.  Everything that belongs to one
configuration, one traffic mix, one cell or one per-layer metric is a file
of its own, so a later PR adds files and one entry and edits nothing.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The benchmark's data files do not describe a runnable cell."""


def _load_json(path: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    cell: Dict[str, Any]            # cells/<name>.json
    config: Dict[str, Any]          # configs/<config>.json
    traffic: Dict[str, Any]         # traffic/<mix>.json
    end_to_end: List[Dict[str, Any]]   # BENCHMARK.json entries this cell reports
    per_layer: List[Dict[str, Any]]    # layer_metrics/<m>.json of this cell
    bench_dir: str

    def reader(self, metric: Dict[str, Any]):
        """The ``read`` function of the module a per-layer metric names."""
        name = metric["reader"]
        path = os.path.join(self.bench_dir, "readers", f"{name}.py")
        if not os.path.isfile(path):
            raise SpecError(f"metric {metric['name']}: no reader {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_reader_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def load_cell(workload: str, *, bench_dir: str = BENCH_DIR,
              manifest: str | None = None,
              data_dir: str | None = None) -> Cell:
    """``manifest`` and ``data_dir`` (which holds ``cells/`` and
    ``traffic/``) default to the repository's; the tests pass tiny ones."""
    manifest = manifest or os.path.join(
        os.path.dirname(bench_dir), "BENCHMARK.json")
    data_dir = data_dir or bench_dir
    bench = _load_json(manifest)
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise SpecError(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(entries)}")
    entry = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise SpecError(f"{workload}: config {entry['config']!r} not listed")
    root = os.path.dirname(bench_dir)
    cell = _load_json(os.path.join(data_dir, "cells", f"{workload}.json"))
    config = _load_json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = _load_json(
        os.path.join(data_dir, "traffic", f"{entry['traffic']}.json"))
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    listed = {m["name"]: m for m in bench["per_layer"]}
    per_layer = []
    for name in cell["per_layer"]:
        meta = _load_json(
            os.path.join(bench_dir, "layer_metrics", f"{name}.json"))
        if name not in listed:
            raise SpecError(f"{workload}: metric {name!r} is not in "
                            "BENCHMARK.json's per_layer")
        per_layer.append({"name": name, **meta})
    return Cell(name=workload, chips=int(entry["chips"]), why=entry["why"],
                cell=cell, config=config, traffic=traffic,
                end_to_end=end_to_end, per_layer=per_layer,
                bench_dir=bench_dir)
