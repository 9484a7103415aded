"""Reading the program's own spans (``bench/lib/program_spans.py``), on
synthetic spans and synthetic device intervals, and once on a smoke-size
run on the CPU."""
import time
import types

import numpy as np
import pytest

from bench.lib import harness, program_spans as ps, runner, trace
from bench.tests import smoke

MS = 1_000_000


def _cols(rows):
    """Ring columns from ``(name, parent, start, end, value)`` rows."""
    names, parents, starts, ends, values = zip(*rows)
    return {"name": np.array(names), "parent": np.array(parents),
            "lane": np.array(["event"] * len(rows)),
            "start_ns": np.array(starts, np.int64),
            "end_ns": np.array(ends, np.int64),
            "step": np.zeros(len(rows), np.int64),
            "value": np.array(values, np.int64)}


@pytest.fixture
def ring(monkeypatch):
    """Install synthetic columns as the program's ring."""
    from repro import tracing
    held = {"cols": None, "lost": 0}

    def spans(since_ns=None):
        cols = held["cols"]
        keep = cols["end_ns"] >= (since_ns or 0)
        return {k: v[keep] for k, v in cols.items()}

    monkeypatch.setattr(tracing, "spans", spans)
    monkeypatch.setattr(tracing, "overwritten_before", lambda: held["lost"])
    return held


def _record(t0_ms, t1_ms, steps=()):
    return types.SimpleNamespace(t_w0=t0_ms / 1e3, t_w1=t1_ms / 1e3,
                                 steps=list(steps))


def test_only_spans_that_start_in_the_window_count(ring):
    ring["cols"] = _cols([
        ("step", "", 90 * MS, 130 * MS, 0),        # starts before
        ("pack", "step", 95 * MS, 105 * MS, 7),
        ("step", "", 130 * MS, 170 * MS, 0),
        ("pack", "step", 131 * MS, 141 * MS, 5),
        ("state_gather", "step", 141 * MS, 151 * MS, 9),
        ("state_park", "step", 160 * MS, 161 * MS, 2),
        ("step", "", 170 * MS, 210 * MS, 0),
        ("pack", "step", 171 * MS, 175 * MS, 5),
        ("step", "", 205 * MS, 240 * MS, 0),      # starts after
        ("compile", "step", 206 * MS, 230 * MS, 1),
    ])
    rec = _record(100, 200)
    assert ps.per_step_ms(rec, ["pack"]) == pytest.approx(7.0)
    assert ps.per_step_value(rec, ["pack"]) == pytest.approx(5.0)
    assert ps.per_step_ms(rec, ["state_gather", "state_park"]) == \
        pytest.approx(5.5)
    assert ps.per_step_value(rec, ["state_gather", "state_park"]) == \
        pytest.approx(5.5)
    assert ps.count(rec, "compile") == 0.0


def test_nothing_when_the_window_start_was_overwritten(ring):
    ring["cols"] = _cols([("step", "", 120 * MS, 150 * MS, 0),
                          ("pack", "step", 121 * MS, 131 * MS, 1)])
    rec = _record(100, 200)
    assert ps.per_step_ms(rec, ["pack"]) == pytest.approx(10.0)
    ring["lost"] = 100 * MS             # a record ending at t_w0 is gone
    assert ps.per_step_ms(rec, ["pack"]) is None
    assert ps.per_step_value(rec, ["pack"]) is None
    assert ps.count(rec, "compile") is None


def test_nothing_without_the_recorder(monkeypatch):
    import sys

    import repro
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr(repro, "tracing")
    rec = _record(100, 200)
    assert ps.per_step_ms(rec, ["pack"]) is None
    assert ps.count(rec, "compile") is None


def test_idle_intervals_are_cut_to_the_window():
    ops = [trace.Op("a", 0, 20), trace.Op("b", 30, 50), trace.Op("c", 40, 60),
           trace.Op("d", 90, 130)]
    summary = types.SimpleNamespace(chips=[trace.Chip(ops, [])])
    assert ps.idle_intervals(summary, (10, 100)) == [(20, 30), (60, 90)]
    assert ps.idle_intervals(summary, (-10, 140)) == [
        (-10, 0), (20, 30), (60, 90), (130, 140)]


def test_clock_offset_is_the_median_over_matched_steps():
    shift = 5_000_000_123
    starts_ms = [100.0, 137.5, 175.2, 212.9, 250.1, 287.0]
    rec = _record(99.0, 300.0, [(a / 1e3, (a + 30) / 1e3)
                                for a in [60.0] + starts_ms + [320.0]])
    host = [(int(99.0 * MS) + shift + 400, int(300 * MS) + shift,
             harness.WINDOW_SPAN)]
    for k, a in enumerate(starts_ms):
        jitter = 7_000_000 if k == 2 else 300       # one stray pair
        lo = int(a * MS) + shift - jitter
        host.append((lo, lo + 30 * MS, "step"))
        host.append((lo + 2_000, lo + 29 * MS, "step"))   # program's
    summary = types.SimpleNamespace(host_spans=host)
    assert ps.clock_offset_ns(summary, rec) == shift - 300
    assert len(ps.outermost(host, "step")) == len(starts_ms)


def test_idle_goes_to_the_innermost_span():
    spans = [(0, 100, "step"), (10, 40, "pack"), (20, 25, "gc"),
             (50, 90, "collect"), (60, 80, "fetch")]
    idle = [(5, 30), (45, 70), (95, 120)]
    got = ps.attribute(idle, spans)
    assert got == {"step": 5 + 5 + 5, "pack": 15, "gc": 5, "collect": 10,
                   "fetch": 10, ps.OTHER: 20}
    assert sum(got.values()) == sum(b - a for a, b in idle)
    assert ps.attribute([], spans) == {}


def test_idle_share_on_the_trace_clock(ring):
    shift = 1_000
    ring["cols"] = _cols([("step", "", 100 * MS, 140 * MS, 0),
                          ("pack", "step", 101 * MS, 111 * MS, 0)])
    rec = _record(100, 200, [(0.100, 0.140)])
    host = [(100 * MS + shift, 200 * MS + shift, harness.WINDOW_SPAN),
            (100 * MS + shift, 140 * MS + shift, "step")]
    ops = [trace.Op("x", 105 * MS + shift, 200 * MS + shift)]
    summary = types.SimpleNamespace(host_spans=host,
                                    chips=[trace.Chip(ops, [])])
    run = types.SimpleNamespace(record=rec, trace=summary)
    # Idle from 100 to 105 ms, of which 101-105 under pack: 4 of 100 ms.
    assert ps.idle_share(run, ["pack"]) == pytest.approx(4.0)
    ops[0] = trace.Op("x", 120 * MS + shift, 200 * MS + shift)
    assert ps.idle_share(run, ["pack"]) == pytest.approx(10.0)
    assert ps.idle_share(types.SimpleNamespace(record=rec, trace=None),
                         ["pack"]) is None


def test_host_span_metrics_read_a_smoke_run():
    from bench.lib import cells, traffic
    cell = smoke.cell("scnn_tracking_p80")
    config, arch = cell.config, cell.arch
    pool = traffic.make_pool(5, cell.mix, arch.sensors(config),
                             config["window_us"])
    server = harness.Server(config, 1, arch, arch.make_weights(5, config))
    server.warm(pool)
    rec = harness.serve(server, cell.mix, cell.mix["heads"], 5, pool, 1.0,
                        time.perf_counter(), 1)
    run = runner.Run(cell=cell, record=rec)
    got = cells.read_metrics(cell.per_layer, run)
    assert got["compiles_in_window.lat"]["value"] == 0.0
    assert got["state_move_ms.lat"]["value"] > 0
    assert got["state_ops_per_step.lat"]["value"] > 0
    assert "idle_in_state_move_share.lat" not in got      # no trace
