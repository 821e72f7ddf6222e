"""Everything the harness knows about a cell comes from here: the manifest
(``BENCHMARK.json``) names a cell's configuration and traffic mix; the files
``cells/<cell>.json``, ``configs/<config>.json`` and ``traffic/<mix>.json``
hold the rest; code is found by the names those files give
(``runners/<kind>.py``, ``generators/<kind>.py``,
``layer_metrics/<reader>.py``, ``roofline/<kernel>.py``). No name of a
cell, model or mix appears in code.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
PACKAGE = os.path.basename(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None      # per-layer metrics only
    moves: Optional[str] = None

    @property
    def reader(self) -> str:
        """``[<scope>.]<reader>``: the scope only keeps names apart where
        one reader serves cells whose end-to-end metrics differ."""
        return self.name.rsplit(".", 1)[-1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: Dict[str, Any]           # configs/<config>.json
    traffic: Dict[str, Any]          # traffic/<mix>.json
    deploy: Dict[str, Any]           # cells/<cell>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def runner(self) -> str:
        return self.deploy["runner"]


def _applies(entry: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def _metric(entry: Dict[str, Any]) -> Metric:
    return Metric(**{k: v for k, v in entry.items() if k != "workloads"
                     and k != "bound"})


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> Cell:
    manifest = load_manifest(root)
    rows = [w for w in manifest["workloads"] if w["name"] == name]
    if not rows:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise SystemExit(f"unknown workload {name!r}; the manifest has: {known}")
    row = rows[0]
    cfg_row = next(c for c in manifest["configs"] if c["name"] == row["config"])
    bench = os.path.join(root, PACKAGE)
    return Cell(
        name=name, chips=int(row["chips"]), why=row["why"],
        config_name=row["config"], traffic_name=row["traffic"],
        config=load_json(os.path.join(root, cfg_row["file"])),
        traffic=load_json(os.path.join(bench, "traffic",
                                       row["traffic"] + ".json")),
        deploy=load_json(os.path.join(bench, "cells", name + ".json")),
        end_to_end=[_metric(m) for m in manifest["end_to_end"]
                    if _applies(m, name)],
        per_layer=[_metric(m) for m in manifest["per_layer"]
                   if _applies(m, name)])


def load_plugin(group: str, name: str):
    """``benchmarks/<group>/<name>.py``, found by name."""
    return importlib.import_module(f"{PACKAGE}.{group}.{name}")
