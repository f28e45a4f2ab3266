"""Everything of a cell, found by the names in ``BENCHMARK.json``.

- ``bench/configs/<config>.json``   the deployment (the file named there)
- ``bench/traffic/<traffic>.json``  the traffic mix; its ``kind`` names
                                    the driver, ``bench/drivers/<kind>.py``
- ``bench/checks/<cell>.json``      the limit of each compared number
- ``bench/metrics/<metric>.py``     one reader per per-layer metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

__all__ = ["BENCH", "ROOT", "Cell", "load_cell", "load_benchmark",
           "metric_reader"]

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    checks: Dict[str, float]
    end_to_end: List[dict]       # the end-to-end metrics this cell reports
    per_layer: List[dict]        # the per-layer metrics read in this cell


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in names and _reports(m, name)]
    here = os.path.join(root, "bench")
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(os.path.join(root, cfg["file"])),
                traffic=_json(os.path.join(here, "traffic",
                                           w["traffic"] + ".json")),
                checks=_json(os.path.join(here, "checks", name + ".json")),
                end_to_end=e2e, per_layer=layer)


def metric_reader(name: str, root: str = ROOT
                  ) -> Callable[[dict], Optional[float]]:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
