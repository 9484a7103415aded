"""The benchmark's registry: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one cell is found by name:

* the cell (``workloads[]`` in ``BENCHMARK.json``): its configuration,
  its traffic mix and its chip count;
* the configuration: the JSON file that ``configs[].file`` names;
* the configuration's adapter: ``bench/arch/<arch>.py``, ``arch`` being
  the configuration's key of that name. It is a module of plain
  functions, the only place that knows the network: ``sensors(config)``
  (what the traffic generator makes), ``make_weights(seed, config)``,
  ``build(config, params, engine_config)`` (the program's engines, one
  per wing), ``shape_keys(config, slots, pool, window_us)``,
  ``served_row(window, config)`` and ``reference_rows(params, pool,
  sample, config, precision)`` (what the check compares),
  ``window_flops(config)`` and ``kernel_work(config, slots)``;
* the traffic mix: ``bench/traffic/<traffic>.json`` (see
  :mod:`bench.lib.traffic`);
* each metric: a reader ``bench/metrics/<metric name>.py`` with a
  ``read(run)`` function that returns the number, or ``None`` when the
  run holds nothing to read it from.

A later cell, configuration (with its adapter and reference), mix or
metric is new files plus new entries; no file here changes. Every path
is under a root, the checkout's by default.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, Dict, List, Optional

from bench.lib import traffic as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = "BENCHMARK.json"


def _path(root: str, *parts: str) -> str:
    return os.path.join(root, "bench", *parts)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's content
    mix: dict               # the traffic mix, checked (traffic.load)
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    arch: ModuleType        # the configuration's adapter
    root: str               # where its files were found


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, SPEC)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(path: str) -> ModuleType:
    """The Python file ``path`` as a module, loaded once per process."""
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        f"bench_{stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, spec: Optional[dict] = None, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, adapter, mix and
    metrics, from the files under ``root``."""
    spec = spec if spec is not None else load_spec(root)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        mix=tr.load(w["traffic"], _path(root, "traffic",
                                        f"{w['traffic']}.json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        arch=load_module(_path(root, "arch", f"{config['arch']}.py")),
        root=root)


def reader(metric: str, root: str = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    return load_module(_path(root, "metrics", f"{metric}.py")).read


def read_metrics(entries: List[dict], run) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for each entry whose reader found
    something to read."""
    out = {}
    for m in entries:
        value = reader(m["name"], run.cell.root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
