"""Spans and counters of the serving path, on the host's clock.

``span(name, lane=..., step=..., value=...)`` is a context manager that
times one piece of host work. Each span records its name, the name of
the span that encloses it (its parent), its start and end on
``time.perf_counter_ns`` (the clock ``time.perf_counter`` reads), the
step it belongs to, the lane (the engine modality) and one integer
``value``: bytes packed, eager device operations issued, windows
accounted, a collection's generation. A counter is a span's value, so
spans and counters are one record type. A span that gives no ``lane``
or ``step`` takes its parent's.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name:
a flag check while no profiler runs, and a host event on the device
trace's clock while one does.

Records go into a preallocated ring of numpy columns (:data:`CAPACITY`
records), so the recorder keeps no Python object per span for the
garbage collector to walk. It is always on. Garbage collections that
take at least :data:`GC_MIN_NS` are recorded as ``gc`` spans, with the
generation as their value.

Reading: :func:`spans` returns the columns, :func:`overwritten_before`
says up to when the ring has lost records, and :func:`totals` gives the
count, seconds and summed value per name since the process started.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict, Optional

import jax
import numpy as np

__all__ = ["Recorder", "Span", "span", "spans", "overwritten_before",
           "totals", "CAPACITY", "GC_MIN_NS"]

CAPACITY = 1 << 16      # a 60 s window and its drain at ~30 steps/s
GC_MIN_NS = 100_000     # shorter collections are not recorded
MAX_NAMES = 256         # distinct span names and lanes in one process


class Span:
    """One open span. ``value`` may be set until the span closes."""

    __slots__ = ("name", "lane", "step", "value", "parent", "_rec",
                 "_ann", "_start")

    def __init__(self, rec: "Recorder", name: str, lane: Optional[str],
                 step: Optional[int], value: int):
        self._rec = rec
        self.name, self.lane, self.step, self.value = name, lane, step, value

    def __enter__(self) -> "Span":
        stack = self._rec._stack()
        outer = stack[-1] if stack else None
        self.parent = outer.name if outer else ""
        if self.lane is None:
            self.lane = outer.lane if outer else ""
        if self.step is None:
            self.step = outer.step if outer else -1
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._write(self.name, self.parent, self.lane, self.step,
                         self._start, end, self.value)


class Recorder:
    """A ring of span records; the process keeps one (:data:`RECORDER`)."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self._lock = threading.RLock()
        self._local = threading.local()
        self._ids: Dict[str, int] = {"": 0}
        self._labels = [""]
        self._name = np.zeros(capacity, np.int16)
        self._parent = np.zeros(capacity, np.int16)
        self._lane = np.zeros(capacity, np.int16)
        self._start = np.zeros(capacity, np.int64)
        self._end = np.zeros(capacity, np.int64)
        self._step = np.zeros(capacity, np.int64)
        self._value = np.zeros(capacity, np.int64)
        self._written = 0
        self._lost_end = 0
        # Totals per name id (plain ints: cheaper to add to than numpy's).
        self._count = [0] * MAX_NAMES
        self._ns = [0] * MAX_NAMES
        self._sum = [0] * MAX_NAMES
        self._gc_start: Optional[int] = None
        self._gc_ann = None

    def span(self, name: str, *, lane: Optional[str] = None,
             step: Optional[int] = None, value: int = 0) -> Span:
        """A span to open with ``with``; see the module docstring."""
        return Span(self, name, lane, step, value)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _id(self, label: str) -> int:
        k = self._ids.get(label)
        if k is None:
            if len(self._labels) == MAX_NAMES:
                raise ValueError(f"more than {MAX_NAMES} span names and "
                                 f"lanes; cannot add {label!r}")
            k = self._ids[label] = len(self._labels)
            self._labels.append(label)
        return k

    def _write(self, name: str, parent: str, lane: str, step: int,
               start: int, end: int, value: int) -> None:
        with self._lock:
            # Take the slot first: a collection recorded from inside this
            # call (the gc hook) then writes the next one.
            n = self._written
            self._written = n + 1
            i = n % self.capacity
            if n >= self.capacity:
                self._lost_end = max(self._lost_end, int(self._end[i]))
            k = self._id(name)
            self._name[i], self._parent[i] = k, self._id(parent)
            self._lane[i] = self._id(lane)
            self._start[i], self._end[i] = start, end
            self._step[i], self._value[i] = step, value
            self._count[k] += 1
            self._ns[k] += end - start
            self._sum[k] += value

    def on_gc(self, phase: str, info: dict) -> None:
        """A ``gc.callbacks`` hook: a ``gc`` span per collection of at
        least :data:`GC_MIN_NS`, under the span the collection
        interrupted."""
        if phase == "start":
            self._gc_ann = jax.profiler.TraceAnnotation("gc")
            self._gc_ann.__enter__()
            self._gc_start = time.perf_counter_ns()
            return
        start, self._gc_start = self._gc_start, None
        if start is None:
            return
        end = time.perf_counter_ns()
        self._gc_ann.__exit__(None, None, None)
        self._gc_ann = None
        if end - start >= GC_MIN_NS:
            stack = self._stack()
            outer = stack[-1] if stack else None
            self._write("gc", outer.name if outer else "",
                        outer.lane if outer else "",
                        outer.step if outer else -1, start, end,
                        int(info.get("generation", -1)))

    def spans(self, since_ns: Optional[int] = None) -> Dict[str, np.ndarray]:
        """The records the ring holds, as columns ordered by start (a
        parent before its children): ``name``, ``parent``, ``lane``
        (strings), ``start_ns``, ``end_ns``, ``step`` (-1 outside any
        step) and ``value``. With ``since_ns``, only records that ended
        at or after that instant."""
        with self._lock:
            n = min(self._written, self.capacity)
            order = (np.arange(n) if self._written <= self.capacity else
                     (np.arange(n) + self._written) % self.capacity)
            cols = {k: getattr(self, f"_{k}")[order] for k in
                    ("name", "parent", "lane", "start", "end", "step",
                     "value")}
            labels = np.array(self._labels)
        idx = (np.arange(n) if since_ns is None
               else np.flatnonzero(cols["end"] >= since_ns))
        idx = idx[np.lexsort((-cols["end"][idx], cols["start"][idx]))]
        return {"name": labels[cols["name"][idx]],
                "parent": labels[cols["parent"][idx]],
                "lane": labels[cols["lane"][idx]],
                "start_ns": cols["start"][idx], "end_ns": cols["end"][idx],
                "step": cols["step"][idx], "value": cols["value"][idx]}

    def overwritten_before(self) -> int:
        """The latest end, in ``perf_counter_ns``, of a record the ring
        has overwritten (0 while it has lost none). Every span that ended
        after it is still held, so a reader whose window starts later
        sees all of its window."""
        return self._lost_end

    def totals(self) -> Dict[str, Dict[str, float]]:
        """Per span name since the process started: ``count``,
        ``seconds`` and the summed ``value``."""
        with self._lock:
            return {label: {"count": self._count[k],
                            "seconds": self._ns[k] / 1e9,
                            "value": self._sum[k]}
                    for label, k in self._ids.items() if self._count[k]}


RECORDER = Recorder()
gc.callbacks.append(RECORDER.on_gc)

span = RECORDER.span
spans = RECORDER.spans
overwritten_before = RECORDER.overwritten_before
totals = RECORDER.totals
