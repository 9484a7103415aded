"""BENCHMARK.json names only what exists, and every cell runs end to
end at smoke size on the CPU (the four-chip cell on four host devices)."""
import json
import os
import re
import time

import pytest

from bench.lib import cells, check, runner, traffic
from bench.tests import smoke

SPEC = cells.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_spec_names_and_files():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for entry in (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
                  + SPEC["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            config = json.load(f)
        assert os.path.isfile(os.path.join(cells.ROOT, "bench", "arch",
                                           f"{config['arch']}.py"))
        if config["arch"] == "scnn":
            assert config["snn"]["time_bins"] == 16
    for w in SPEC["workloads"]:
        limits = traffic.load(w["traffic"])["limits"]
        assert limits and set(limits) <= set(check.NAMES)
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(cells.reader(m["name"]))


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(name):
    c = cells.cell(name)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("name", smoke.CELLS)
def test_cell_runs_and_is_correct_at_smoke_size(name):
    c = smoke.cell(name)
    out = runner.run_cell(c, 2 ** 31 + 77, 1.0, False, time.perf_counter(),
                          chip=False)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in c.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "compared"
    assert out["device"]["count"] >= c.chips
