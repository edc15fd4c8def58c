"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell lives in a file of its own, found by name:

  configs/<config>.json     the model as it is run (``file`` in the entry);
                            its ``family`` names the two files below
  families/<family>.py      the program's config, counts and rehearsal
                            sizes for that architecture
  references/<family>.py    its plain float32 mathematics
  traffic/<traffic>.json    the parameters the one generator reads
  limits/<workload>.json    the limits that decide ``correct``
  metrics/<metric>.py       the reader of one per-layer metric

So a new cell, configuration, architecture, traffic mix or metric is new
files and new entries in ``BENCHMARK.json``, never an edit of a file that
is there.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from functools import lru_cache
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


class SpecError(ValueError):
    """A file that ``BENCHMARK.json`` leads to is missing or incomplete."""


@dataclass(frozen=True)
class Family:
    """One architecture: ``program`` is ``families/<name>.py``, ``math``
    is ``references/<name>.py``."""
    name: str
    program: ModuleType
    math: ModuleType


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    family: Family
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]   # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


@lru_cache(maxsize=None)
def _module(path: str, name: str) -> ModuleType:
    """The module of the file ``path``, loaded once per process."""
    if not os.path.isfile(path):
        raise SpecError(f"no such file: {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_family(name: str, bench_dir: str = HERE) -> Family:
    """The family ``name``: ``families/<name>.py`` and
    ``references/<name>.py`` under ``bench_dir``."""
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", str(name)):
        raise SpecError(f"family {name!r} is not a name")
    return Family(name, *(
        _module(os.path.join(bench_dir, kind, f"{name}.py"),
                f"chipbench_{kind}_{name}")
        for kind in ("families", "references")))


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
    config_path = os.path.join(bench_dir, "configs", f"{w['config']}.json")
    config = _load_json(config_path)
    if "family" not in config:
        raise SpecError(f"{config_path} names no family: give it "
                        f"\"family\", the name of a families/<name>.py")
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(bench_dir, "limits",
                                     f"{workload}.json"))["limits"]
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    layer = [m for m in spec["per_layer"] if _reports(m, workload)]
    return Cell(workload, int(w["chips"]), w["config"], w["traffic"],
                config, load_family(config["family"], bench_dir), traffic,
                limits, e2e, layer)


def metric_reader(name: str, bench_dir: str = HERE
                  ) -> Callable[[Any], Optional[float]]:
    """``read(ctx)`` of ``metrics/<name>.py``."""
    return _module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                   f"chipbench_metric_{name.replace('.', '_')}").read
