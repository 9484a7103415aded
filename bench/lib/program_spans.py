"""The program's own spans (``repro.tracing``), read for the metric readers.

The program records a span around each piece of host work inside
``StreamEngine.step()`` (``assign``, ``pack``, ``state_gather``,
``launch``, ``state_park``, ``collect`` with its ``fetch`` and
``account``; ``compile`` and ``gc`` wherever they happen) in a ring that
lives in the benchmark's own process, on ``time.perf_counter_ns``: the
clock of the run's :class:`~bench.lib.harness.Record`, in nanoseconds.

A span counts toward the measured window when it starts inside it, and
a per-step number is over the program's ``step`` spans that start inside
it. Idle shares also need the device trace: the program's spans are put
on the trace's clock by the median offset between the benchmark's own
``step`` spans in the trace and the same calls in ``record.steps``, and
each idle instant of chip 0 goes to the innermost program span covering
it (the rule of :meth:`bench.lib.trace.Summary.host_label`).

Every reader returns ``None`` when the program has no recorder (an older
checkout) or when its ring lost the start of the window: never a partial
count.
"""
from __future__ import annotations

import bisect
import collections
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from bench.lib import harness

STEP = "step"
OTHER = "other"         # idle time no program span covers
Interval = Tuple[int, int]


def _ns(t: float) -> int:
    return int(round(t * 1e9))


def ring(record) -> Optional[Dict[str, np.ndarray]]:
    """The program's spans that ended at or after the window opened, as
    columns (``repro.tracing.spans``); None without a recorder, or when
    the ring has overwritten any of them."""
    try:
        from repro import tracing
    except ImportError:
        return None
    t0 = _ns(record.t_w0)
    if tracing.overwritten_before() >= t0:
        return None
    return tracing.spans(since_ns=t0)


def window_spans(record) -> Optional[Dict[str, np.ndarray]]:
    """The program's spans that start in the measured window; None as
    for :func:`ring`."""
    cols = ring(record)
    if cols is None:
        return None
    t0, t1 = _ns(record.t_w0), _ns(record.t_w1)
    inside = (cols["start_ns"] >= t0) & (cols["start_ns"] < t1)
    return {k: v[inside] for k, v in cols.items()}


def _steps(cols) -> int:
    return int(np.count_nonzero(cols["name"] == STEP))


def per_step_ms(record, names: Iterable[str]) -> Optional[float]:
    """Mean time per program step of the spans named ``names``, in ms."""
    cols = window_spans(record)
    if cols is None or not _steps(cols):
        return None
    sel = np.isin(cols["name"], list(names))
    took = (cols["end_ns"][sel] - cols["start_ns"][sel]).sum()
    return float(took) / 1e6 / _steps(cols)


def per_step_value(record, names: Iterable[str]) -> Optional[float]:
    """Mean summed value per program step of the spans named ``names``."""
    cols = window_spans(record)
    if cols is None or not _steps(cols):
        return None
    sel = np.isin(cols["name"], list(names))
    return float(cols["value"][sel].sum()) / _steps(cols)


def count(record, name: str) -> Optional[float]:
    """How many spans named ``name`` started in the window."""
    cols = window_spans(record)
    return None if cols is None else float(
        np.count_nonzero(cols["name"] == name))


# -- the device trace ------------------------------------------------------

def outermost(spans: List[Tuple[int, int, str]], name: str
              ) -> List[Interval]:
    """The spans named ``name`` that no other span of that name contains:
    the benchmark's own ``step`` spans, where the program's ``step``
    annotation nests inside each."""
    out: List[Interval] = []
    for a, b, _ in sorted((s for s in spans if s[2] == name),
                          key=lambda s: (s[0], -s[1])):
        if not out or a >= out[-1][1]:
            out.append((a, b))
    return out


def trace_window(summary) -> Optional[Interval]:
    """The measured window on the trace's clock: the longest ``window``
    span, as :func:`bench.lib.trace.from_data` cuts it."""
    marks = [(a, b) for a, b, n in summary.host_spans
             if n == harness.WINDOW_SPAN]
    return max(marks, key=lambda m: m[1] - m[0]) if marks else None


def clock_offset_ns(summary, record) -> Optional[int]:
    """Trace clock minus ``perf_counter_ns``: the median, over the
    benchmark's ``step`` spans in the trace, of each one's start minus
    the start of the same ``step()`` call in ``record.steps`` (the call
    whose start lies nearest, once the window spans' starts are
    matched)."""
    window = trace_window(summary)
    starts = sorted(_ns(a) for a, _ in record.steps)
    traced = outermost(summary.host_spans, STEP)
    if window is None or not starts or not traced:
        return None
    coarse = window[0] - _ns(record.t_w0)
    diffs = []
    for a, _ in traced:
        want = a - coarse
        i = bisect.bisect_left(starts, want)
        near = min(starts[max(i - 1, 0):i + 1], key=lambda s: abs(s - want))
        diffs.append(a - near)
    return int(statistics.median(diffs))


def idle_intervals(summary, window: Interval) -> List[Interval]:
    """The gaps of chip 0 between its busy intervals, inside ``window``."""
    out, at = [], window[0]
    for a, b in summary.chips[0].busy_intervals():
        if a > at:
            out.append((at, min(a, window[1])))
        at = max(at, b)
    if at < window[1]:
        out.append((at, window[1]))
    return [(a, b) for a, b in out if b > a]


def attribute(intervals: List[Interval], spans: List[Tuple[int, int, str]]
              ) -> Dict[str, int]:
    """Nanoseconds of ``intervals`` (sorted, disjoint) under each span
    name: every instant goes to the innermost (shortest) span covering
    it, and to ``"other"`` where none does."""
    out: Dict[str, int] = collections.Counter()
    if not intervals:
        return out
    spans = sorted(spans)
    bounds = sorted({p for a, b in intervals for p in (a, b)}
                    | {p for a, b, _ in spans for p in (a, b)})
    active: List[Tuple[int, int, str]] = []
    i = j = 0
    for p, q in zip(bounds, bounds[1:]):
        while i < len(spans) and spans[i][0] <= p:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] > p]
        while j < len(intervals) and intervals[j][1] <= p:
            j += 1
        if j == len(intervals):
            break
        if intervals[j][0] <= p:
            inner = min(active, key=lambda s: s[1] - s[0], default=None)
            out[inner[2] if inner else OTHER] += q - p
    return out


def on_trace(cols, offset: int) -> List[Tuple[int, int, str]]:
    """The program's spans as ``(start, end, name)`` on the trace's clock."""
    return [(int(a) + offset, int(b) + offset, str(n)) for a, b, n in
            zip(cols["start_ns"], cols["end_ns"], cols["name"])]


def idle_by_span(run) -> Optional[Tuple[Dict[str, int], int]]:
    """Chip 0's idle time in the traced window, in ns per innermost
    program span, and the window's length in ns; None without a trace or
    without the program's spans."""
    if run.trace is None:
        return None
    window = trace_window(run.trace)
    cols = ring(run.record)
    offset = clock_offset_ns(run.trace, run.record)
    if window is None or cols is None or offset is None:
        return None
    idle = idle_intervals(run.trace, window)
    return attribute(idle, on_trace(cols, offset)), window[1] - window[0]


def idle_share(run, names: Iterable[str]) -> Optional[float]:
    """% of the traced window in which chip 0 was idle and the innermost
    program span was one of ``names``."""
    got = idle_by_span(run)
    if got is None:
        return None
    by_span, length = got
    return 100.0 * sum(by_span.get(n, 0) for n in names) / length
