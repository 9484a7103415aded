"""The benchmark's registry: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one cell is found by name:

* the cell (``workloads[]`` in ``BENCHMARK.json``): its configuration,
  its traffic mix and its chip count;
* the configuration: the JSON file that ``configs[].file`` names;
* the traffic mix: ``bench/traffic/<traffic>.json`` (see
  :mod:`bench.lib.traffic`);
* each metric: a reader ``bench/metrics/<metric name>.py`` with a
  ``read(run)`` function that returns the number, or ``None`` when the
  run holds nothing to read it from.

A later cell, configuration, mix or metric is new files plus new
entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

from bench.lib import traffic as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = os.path.join(ROOT, "BENCHMARK.json")
METRICS_DIR = os.path.join(ROOT, "bench", "metrics")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's content
    mix: dict               # the traffic mix, checked (traffic.load)
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def load_spec(path: str = SPEC) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, spec: Optional[dict] = None) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    spec = spec if spec is not None else load_spec()
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        mix=tr.load(w["traffic"]),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def reader(metric: str, directory: str = METRICS_DIR) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(directory, f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(entries: List[dict], run) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for each entry whose reader found
    something to read."""
    out = {}
    for m in entries:
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
