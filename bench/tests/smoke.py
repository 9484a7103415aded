"""The benchmark's SCNN cells (configurations with ``"arch": "scnn"``)
cut to a size the CPU runs in seconds: the ``SMOKE``/``TCN_SMOKE``
widths of ``repro.configs.colibries``, 4 slots per chip, small windows
and short pools. For rehearsals and tests only; the cells themselves
run at the published widths. A configuration of another network brings
its own tests."""
from __future__ import annotations

import copy
import dataclasses

from bench.lib import cells

SNN = {"height": 32, "width": 32, "in_channels": 2, "pool0": 4,
       "conv1_features": 4, "conv2_features": 8, "hidden": 32,
       "num_classes": 11, "time_bins": 8}
TCN = {"height": 32, "width": 32, "in_channels": 1, "pool0": 4,
       "conv1_features": 4, "conv2_features": 8, "hidden": 32,
       "num_classes": 11}


SPEC = cells.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]
         if cells.cell(w["name"], SPEC).config["arch"] == "scnn"]


def cell(name: str, heads: int = 0) -> cells.Cell:
    """The cell ``name`` at smoke size (``heads`` per chip if given)."""
    c = cells.cell(name)
    c = dataclasses.replace(c, config=copy.deepcopy(c.config),
                            mix=copy.deepcopy(c.mix))
    c.config["snn"].update(SNN)
    if "tcn" in c.config:
        c.config["tcn"].update(TCN)
    c.config["slots_per_chip"] = 4
    c.mix.update(mean_events=3000, pool_windows=8, warm_s=0.3, check=8)
    if c.mix["fusion"]:
        c.mix["pool_frames"] = 4
    c.mix["heads"] = heads or (4 if c.mix["loop"] == "closed" else 6)
    return c
