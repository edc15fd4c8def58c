"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell lives in a file of its own, found by name:

  configs/<config>.json     the model as it is run (``file`` in the entry)
  traffic/<traffic>.json    the parameters the one generator reads
  limits/<workload>.json    the limits that decide ``correct``
  metrics/<metric>.py       the reader of one per-layer metric

So a new cell, configuration, traffic mix or metric is new files and new
entries in ``BENCHMARK.json``, never an edit of a file that is there.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]   # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(workload: str, root: str = ROOT,
              entry: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with its
    configuration, traffic, limits and metrics resolved by name.

    ``entry`` stands in for a workload entry that ``BENCHMARK.json`` does
    not hold yet (``name``, ``config``, ``traffic``, ``chips``), as a
    cell is rehearsed before it is added."""
    bench_dir = os.path.join(root, "benchmarks", "chip")
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if entry is None and workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = entry or cells[workload]
    config = _load_json(os.path.join(bench_dir, "configs",
                                     f"{w['config']}.json"))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(bench_dir, "limits",
                                     f"{workload}.json"))["limits"]
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    layer = [m for m in spec["per_layer"] if _reports(m, workload)]
    return Cell(workload, int(w["chips"]), w["config"], w["traffic"],
                config, traffic, limits, e2e, layer)


def metric_reader(name: str, bench_dir: str = HERE
                  ) -> Callable[[Any], Optional[float]]:
    """``read(ctx)`` of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
