"""The program's span recorder (``repro.tracing``), through a real
``StreamEngine`` at smoke size, and its ring on its own.

What the benchmark reads from the spans has to hold here: spans nest
under the right parents, a pipelined collect is filed under the step
that dispatched it, state moves are counted only where state moves, a
compile fires once per new shape and never after ``warmup``, and the
ring says when it has lost records."""
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import EngineConfig, SNNConfig, init_snn
from repro.core import events as ev
from repro.serving import StreamEngine


@pytest.fixture(scope="module")
def cfg():
    return SNNConfig(height=32, width=32, time_bins=4, conv1_features=4,
                     conv2_features=8, hidden=32, num_classes=11)


@pytest.fixture(scope="module")
def params(cfg):
    return init_snn(jax.random.PRNGKey(0), cfg)


def _window(rng, k, mean_events=400):
    return ev.synthetic_gesture_events(rng, k % 11, mean_events=mean_events,
                                       height=32, width=32)


def _serve(params, cfg, *, slots, streams, windows, stateful=False,
           depth=1, warm=True):
    """Serve ``windows`` windows on each of ``streams`` streams and return
    the spans recorded meanwhile (and the engine)."""
    eng = StreamEngine(params, cfg, EngineConfig(max_streams=slots,
                                                 pipeline_depth=depth))
    rng = np.random.default_rng(0)
    items = [[_window(rng, s * windows + k) for k in range(windows)]
             for s in range(streams)]
    if warm:
        most = max(w.num_events for ws in items for w in ws)
        eng.warmup([(slots, ev.next_pow2(most), 300_000)])
    since = time.perf_counter_ns()
    handles = [eng.open(stateful=stateful) for _ in range(streams)]
    for k in range(windows):
        for h, ws in zip(handles, items):
            h.submit(ws[k])
    eng.run()
    return tracing.spans(since_ns=since), eng


def _named(cols, name):
    return np.flatnonzero(cols["name"] == name)


def test_spans_nest_under_their_parents(cfg, params):
    cols, _ = _serve(params, cfg, slots=2, streams=2, windows=3, warm=False)
    parent = dict(assign="step", pack="step", launch="step",
                  collect="step", fetch="collect", compile="launch")
    for name, want in parent.items():
        idx = _named(cols, name)
        assert len(idx), name
        assert set(cols["parent"][idx]) == {want}, name
    acct = _named(cols, "account")
    assert set(cols["parent"][acct]) == {"collect"}
    # The engine's accounting counts the windows; the stats loop adds 0.
    assert cols["value"][acct].sum() == 6
    steps = _named(cols, "step")
    assert set(cols["parent"][steps]) == {""}
    assert cols["value"][steps].sum() == 6
    assert set(cols["lane"][_named(cols, "pack")]) == {"event"}
    for i in _named(cols, "pack"):
        assert cols["value"][i] > 0          # bytes of the padded batch
    for name, want in parent.items():
        for i in _named(cols, name):
            outer = [j for j in _named(cols, want)
                     if cols["start_ns"][j] <= cols["start_ns"][i]
                     and cols["end_ns"][i] <= cols["end_ns"][j]]
            assert outer, (name, i)


def test_pipelined_collect_carries_the_step_of_its_dispatch(cfg, params):
    cols, _ = _serve(params, cfg, slots=2, streams=2, windows=3, depth=1)
    steps = _named(cols, "step")
    dispatched = set(cols["step"][_named(cols, "launch")])
    collects = _named(cols, "collect")
    assert len(collects) == 3
    for i in collects:
        inside = [j for j in steps
                  if cols["start_ns"][j] <= cols["start_ns"][i]
                  < cols["end_ns"][j]]
        assert len(inside) == 1
        # Collected one call after its dispatch, filed under the latter.
        assert cols["step"][i] == cols["step"][inside[0]] - 1
        assert cols["step"][i] in dispatched
        for j in _named(cols, "fetch"):
            a, b = cols["start_ns"][i], cols["end_ns"][i]
            if a <= cols["start_ns"][j] < b:
                assert cols["step"][j] == cols["step"][i]


def test_slot_changes_record_state_moves(cfg, params):
    cols, _ = _serve(params, cfg, slots=2, streams=3, windows=3,
                     stateful=True)
    gather, park = _named(cols, "state_gather"), _named(cols, "state_park")
    assert len(gather) and len(park)
    assert set(cols["parent"][gather]) == {"step"}
    assert set(cols["parent"][park]) == {"step"}
    # A step that re-lays the slots issues one compiled program, which
    # also parks the carries of streams that lost their slot; the other
    # steps issue none, and the commit issues no device operation.
    assert set(cols["value"][gather]) == {0, 1}
    assert set(cols["value"][park]) == {0}


def test_identity_fast_path_records_zero(cfg, params):
    cols, _ = _serve(params, cfg, slots=2, streams=2, windows=4,
                     stateful=True)
    gather = _named(cols, "state_gather")
    values = cols["value"][gather]
    # The first dispatch builds the rows from the zero state in one
    # program; after that every stream keeps its slot and its carry
    # stays in place.
    assert len(values) == 4 and values[0] == 1
    assert list(values[1:]) == [0, 0, 0]
    assert set(cols["value"][_named(cols, "state_park")]) == {0}


def test_stateless_lane_records_no_state_spans(cfg, params):
    cols, _ = _serve(params, cfg, slots=2, streams=3, windows=2)
    assert len(_named(cols, "step"))
    assert not len(_named(cols, "state_gather"))
    assert not len(_named(cols, "state_park"))


def test_compile_fires_once_per_shape_and_never_after_warmup(cfg, params):
    eng = StreamEngine(params, cfg, EngineConfig(max_streams=2,
                                                 pipeline_depth=1))
    rng = np.random.default_rng(3)
    small = [_window(rng, k, 300) for k in range(2)]
    large = [_window(rng, k, 3000) for k in range(2)]
    keys = [(2, ev.next_pow2(max(w.num_events for w in ws)), 300_000)
            for ws in (small, large)]
    assert keys[0] != keys[1]
    since = time.perf_counter_ns()
    eng.warmup(keys)
    eng.warmup(keys)                    # cached: no second compile
    warmed = tracing.spans(since_ns=since)
    idx = _named(warmed, "compile")
    assert len(idx) == 2 and set(warmed["value"][idx]) == {1}
    assert set(warmed["lane"][idx]) == {"event"}
    since = time.perf_counter_ns()
    for w in small + large:
        eng.open().submit(w)
    eng.run()
    assert not len(_named(tracing.spans(since_ns=since), "compile"))


def test_ring_reports_what_it_overwrote():
    rec = tracing.Recorder(capacity=8)
    for k in range(8):
        with rec.span("s", value=k):
            pass
    assert rec.overwritten_before() == 0
    first_end = rec.spans()["end_ns"][0]
    with rec.span("s", value=8):
        pass
    assert rec.overwritten_before() == first_end
    cols = rec.spans()
    assert list(cols["value"]) == list(range(1, 9))
    assert len(rec.spans(since_ns=int(cols["end_ns"][-1]))["name"]) == 1


def test_totals_match_the_spans():
    rec = tracing.Recorder(capacity=64)
    for k in range(5):
        with rec.span("outer", lane="event", step=k) as outer:
            with rec.span("inner", value=3):
                pass
            outer.value = k
    cols = rec.spans()
    totals = rec.totals()
    assert set(totals) == {"outer", "inner"}
    for name in totals:
        sel = cols["name"] == name
        took = (cols["end_ns"][sel] - cols["start_ns"][sel]).sum()
        assert totals[name]["count"] == sel.sum() == 5
        assert totals[name]["seconds"] == pytest.approx(took / 1e9)
        assert totals[name]["value"] == cols["value"][sel].sum()
    inner = cols["name"] == "inner"
    # A child takes its parent's lane and step when it gives none.
    assert set(cols["parent"][inner]) == {"outer"}
    assert set(cols["lane"][inner]) == {"event"}
    assert list(cols["step"][inner]) == list(range(5))


def test_long_collections_are_gc_spans():
    rec = tracing.Recorder(capacity=16)
    with rec.span("step", step=7):
        rec.on_gc("start", {"generation": 2})
        time.sleep(2 * tracing.GC_MIN_NS / 1e9)
        rec.on_gc("stop", {"generation": 2})
        rec.on_gc("start", {"generation": 0})
        rec.on_gc("stop", {"generation": 0})    # too short to keep
    cols = rec.spans()
    gc_ = cols["name"] == "gc"
    assert gc_.sum() == 1
    assert cols["value"][gc_][0] == 2
    assert cols["parent"][gc_][0] == "step" and cols["step"][gc_][0] == 7
