"""Find a cell's pieces by name: `BENCHMARK.json` at the root of the
checkout, the traffic mix `workloads/<cell>.json`, the configuration
`configs/<config>.json` with its module `configs/<config>.py`, and one
reader `metrics/<metric>.py` per metric. Adding a cell, a configuration or
a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import List, Tuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str) -> ModuleType:
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in bench['workloads']]})")


def staged_entry(bench: dict, name: str) -> dict:
    """The cell's entry in BENCHMARK.json; for a traffic file that is not
    there yet, a one-chip entry made from the file, so that a cell's limits
    can be read before it goes in."""
    try:
        return cell_entry(bench, name)
    except KeyError:
        return {"name": name, "config": workload(name)["config"],
                "traffic": name, "chips": 1}


def workload(name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "workloads", name + ".json"))


def config(name: str) -> Tuple[dict, ModuleType]:
    """-> (the configuration as run, its module)."""
    base = os.path.join(BENCH_DIR, "configs", name)
    return _json(base + ".json"), _module(base + ".py",
                                          "benchcfg_" + name.replace("-", "_"))


def metric(name: str) -> ModuleType:
    return _module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                   "benchmetric_" + name.replace(".", "_"))


def metrics_of(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The metric entries a run of `cell` reports: its end-to-end metrics,
    or with a trace its per-layer ones. An entry without `workloads`
    belongs to every cell."""
    kind = "per_layer" if traced else "end_to_end"
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]
