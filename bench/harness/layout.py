"""Finds every piece of a cell by name.

``BENCHMARK.json`` names the cells; each cell names a configuration (its
``file`` under ``bench/configs``) and a traffic mix
(``bench/traffic/<traffic>.json``).  A configuration names its model, whose
plain reference and data generator live in ``bench/models/<model>.py`` and
whose FLOP count lives in ``bench/flops/<model>.py``.  A traffic mix names
its scenario (``bench/scenarios/<scenario>.py``) and server schedule
(``bench/schedules/<schedule>.py``).  A per-layer metric is
``bench/metrics/<metric>.py``; a cell's correctness limits are
``bench/limits/<cell>.json``.  Adding any of them is a new file plus a new
entry, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``bench/<kind>/<name>.py`` as a module (file names are names)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: str

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.bench_dir)


def find_cell(name: str, root: str = ROOT,
              bench_dir: Optional[str] = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench_dir = bench_dir or os.path.join(root, "bench")
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    limits = load_json(os.path.join(bench_dir, "limits", name + ".json"))

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=int(w["chips"]), config=cfg,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)],
                bench_dir=bench_dir)
