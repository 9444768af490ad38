"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the one ``configs`` gives for it; the
traffic mix is ``traffic/<traffic>.json``, which names the window
driver (``drivers/<driver>.py``) and holds its parameters; each
per-layer metric is read by ``metrics/<metric>.py``.  Adding a cell, a
configuration or a metric therefore adds files and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    config_dir: str
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(entry: Dict[str, Any], cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves", entry["name"]) in e2e_names


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration, traffic and
    the metrics it reports.  Raises ``KeyError`` for an unknown name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = os.path.join(root, configs[w["config"]]["file"])
    traffic_path = os.path.join(root, "bench", "traffic",
                                w["traffic"] + ".json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=load_json(cfg_path),
                config_dir=os.path.dirname(cfg_path),
                traffic_name=w["traffic"], traffic=load_json(traffic_path),
                end_to_end=e2e, per_layer=per_layer)


def driver(cell: Cell, root: str = ROOT):
    name = cell.traffic["driver"]
    return load_module(os.path.join(root, "bench", "drivers", name + ".py"),
                       f"bench_driver_{name}")


def reference(cell: Cell):
    """The configuration's plain reference, the module its file names
    beside it."""
    name = cell.config["reference"]
    return load_module(os.path.join(cell.config_dir, name),
                       "bench_ref_" + os.path.splitext(name)[0])


def reader(metric: str, root: str = ROOT):
    return load_module(os.path.join(root, "bench", "metrics", metric + ".py"),
                       "bench_metric_" + metric.replace(".", "_"))
