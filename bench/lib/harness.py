"""One run of one cell: set-up, the measured window, the drain.

The timed path is the served one, with nothing bypassed:
``StreamEngine(engines=...)``, the engines being what the
configuration's adapter builds (``bench/arch/<arch>.py``, ``build``),
with ``EngineConfig(max_streams=slots, pipeline_depth=..., fuse_fc=...,
recovery=None)`` from the configuration, windows in through
``StreamHandle.submit`` or ``FusionSession.submit``, results out of
``StreamEngine.step()`` and routed to their head by stream id.

Clock: every time is ``time.perf_counter()``. An open-loop window is
due when its 300 ms of sensor data has closed (the head's phase plus a
whole number of periods); a closed-loop window is due when it is
submitted. A window's latency runs from its due time to the return of
the ``step()`` call that hands back its result (for a fused head, the
call that completes its tick).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.lib import traffic as tr

DRAIN_S = 60.0          # bound on the drain after the measured window
WINDOW_SPAN = "window"  # host span around the measured window


def served(result) -> Optional[tuple]:
    """What the check needs of one served result: ``(label, pwm, logits,
    rates)``, rates being the engine's per-layer firing rates
    (``breakdown["firing_rates"]``) as ``((layer, rate), ...)``, None
    where it reports none (a fused tick). A plain tuple of
    numbers and arrays, which Python's garbage collector stops tracking:
    thousands of windows recorded during the measured window must not
    make its full collections longer or more frequent."""
    if result is None:
        return None
    rates = result.breakdown.get("firing_rates")
    return (int(np.asarray(result.label_pred).reshape(-1)[0]),
            np.array(result.pwm), np.array(result.logits),
            None if rates is None else tuple(
                (k, float(v)) for k, v in sorted(rates.items())))


@dataclasses.dataclass
class Window:
    """One window as the check and the metric readers see it, built
    from the run's record once the run is over."""
    head: int
    k: int                      # the head's k-th window (its seq)
    due: float
    ev: int                     # pool index of the event window
    fr: Optional[int] = None    # pool index of the frame (fused heads)
    submit: Optional[float] = None
    done: Optional[float] = None
    status: Optional[str] = None
    out: Optional[tuple] = None     # served(): the result (fused: tick)
    wings: Optional[dict] = None    # fused heads: served() of each wing


@dataclasses.dataclass
class Record:
    """What a run observed. While it serves, only plain tuples go in
    (``sent``, ``got``); :attr:`windows` joins them afterwards."""
    seconds: float
    t_w0: float = 0.0
    t_w1: float = 0.0
    # (head, k) -> (due, event pool index, frame pool index, submitted)
    sent: Dict[Tuple[int, int], tuple] = dataclasses.field(
        default_factory=dict)
    # (head, k) -> (done, status, served(), (event, frame) served())
    got: Dict[Tuple[int, int], tuple] = dataclasses.field(
        default_factory=dict)
    steps: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)         # step() calls: (start, end)
    setup_s: float = 0.0
    memory_peak_bytes: int = 0
    _windows: Optional[dict] = None

    @property
    def windows(self) -> Dict[Tuple[int, int], Window]:
        if self._windows is None:
            self._windows = {}
            for (h, k), (due, ev, fr, sub) in self.sent.items():
                done, status, out, wings = self.got.get(
                    (h, k), (None, None, None, None))
                self._windows[(h, k)] = Window(
                    head=h, k=k, due=due, ev=ev, fr=fr, submit=sub,
                    done=done, status=status, out=out,
                    wings=None if wings is None else dict(
                        zip(("event", "frame"), wings)))
        return self._windows

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t_w0 <= t < self.t_w1

    def due_in_window(self) -> List[Window]:
        return [w for w in self.windows.values() if self.in_window(w.due)]


class Spans:
    """Host spans: ``jax.profiler.TraceAnnotation`` while tracing (so the
    trace can say what the host did in a device gap), nothing otherwise."""

    def __init__(self, tracing: bool):
        self._ann = None
        if tracing:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self._ann else contextlib.nullcontext()


class Server:
    """The program under test, built for one cell: the serving layer's
    settings and mesh here, the engines from the configuration's
    adapter."""

    def __init__(self, config: dict, chips: int, arch, params):
        from repro.core import EngineConfig
        from repro.serving import StreamEngine
        self.arch, self.cell_config = arch, config
        self.slots = config["slots_per_chip"] * chips
        mesh = None
        if chips > 1:
            from repro.distributed import make_mesh
            mesh = make_mesh(chips)
        self.window_us = config["window_us"]
        self.config = EngineConfig(
            max_streams=self.slots, pipeline_depth=config["pipeline_depth"],
            fuse_fc=config.get("fuse_fc", False), recovery=None, mesh=mesh,
            duration_us=self.window_us)
        self.engine = StreamEngine(
            engines=arch.build(config, params, self.config),
            config=self.config)

    def warm(self, pool: tr.Pool) -> Dict[str, tuple]:
        """Compile the executables this cell's traffic uses (the
        adapter's ``shape_keys``), or load them from the persistent
        cache, before any traffic; returns their keys by wing."""
        keys = self.arch.shape_keys(self.cell_config, self.slots, pool,
                                    self.window_us)
        for modality, key in keys.items():
            self.engine.warmup([key], modality=modality)
        return keys

    def hlo_texts(self, keys: Dict[str, tuple]) -> Dict[str, str]:
        """Each wing's compiled step as HLO text, for the trace reduction
        to tell the wings and kernels apart."""
        return {m: self.engine.engines[m]._exe[key].as_text()
                for m, key in keys.items()}


def to_program(pool: tr.Pool):
    """The pool as the program's own window types."""
    from repro.core import events as ev, frames as fr
    events = [ev.EventWindow(x=w.x, y=w.y, t=w.t, p=w.p,
                             duration_us=w.duration_us, label=w.label)
              for w in pool.events]
    frames = [fr.FrameWindow(pixels=f.pixels, duration_us=f.duration_us,
                             label=f.label) for f in pool.frames]
    return events, frames


class Load:
    """The cell's heads, their schedule, and the serving loop."""

    def __init__(self, server: Server, mix: dict, heads: int, seed: int,
                 pool: tr.Pool, record: Record, spans: Spans):
        from repro.serving import FusionSession
        self.mix, self.seed, self.rec, self.span = mix, seed, record, spans
        self.eng = server.engine
        self.events, self.frames = to_program(pool)
        self.n_heads = heads
        self.fusion = mix["fusion"]
        self.closed = mix["loop"] == "closed"
        self.period = mix["period_ms"] / 1e3
        self.phase = tr.phases_ms(seed, heads, mix["period_ms"]) / 1e3
        self.heads = []
        self.by_stream = {}
        for h in range(heads):
            sid = f"h{h}"
            if self.fusion:
                s = FusionSession(self.eng, session_id=sid,
                                  stateful=mix["stateful"])
                self.heads.append(s)
                self.by_stream[s.event.stream_id] = (h, s, "event")
                self.by_stream[s.frame.stream_id] = (h, s, "frame")
            else:
                handle = self.eng.open(modality="event", stream_id=sid,
                                       stateful=mix["stateful"])
                self.heads.append(handle)
                self.by_stream[sid] = (h, None, None)
        self.next_k = [0] * heads
        self.outstanding = [0] * heads
        self.wings = {}               # fused heads: wings in before ticks
        # Open loop: heads in due order, walked as (period, phase rank).
        self.order = np.argsort(self.phase, kind="stable")
        self.n_sent = 0               # open-loop windows sent so far
        self.t0 = 0.0
        self.submitting = True

    # -- submission --------------------------------------------------------

    def _submit(self, h: int, due: float, now: float) -> None:
        k = self.next_k[h]
        ev = tr.window_index(self.seed, h, k, len(self.events))
        fr = None
        if self.fusion:
            fr = tr.window_index(self.seed + 3, h, k, len(self.frames))
            seq = self.heads[h].submit(self.events[ev], self.frames[fr])
        else:
            seq = self.heads[h].submit(self.events[ev])
        if seq != k:
            raise RuntimeError(f"head {h}: the program numbered window {k} "
                               f"as {seq}")
        self.rec.sent[(h, k)] = (due, ev, fr, now)
        self.next_k[h] = k + 1
        self.outstanding[h] += 1

    def next_due(self) -> float:
        """Due time of the next open-loop window."""
        rnd, i = divmod(self.n_sent, self.n_heads)
        return self.t0 + self.phase[self.order[i]] + rnd * self.period

    def submit_due(self, now: float, until: Optional[float] = None) -> None:
        """Open loop: send every window due by ``now`` (and before
        ``until``, when given)."""
        while True:
            due = self.next_due()
            if due > now or (until is not None and due >= until):
                return
            self._submit(int(self.order[self.n_sent % self.n_heads]), due,
                         now)
            self.n_sent += 1

    def top_up(self, now: float, heads=None) -> None:
        """Closed loop: keep ``queued_per_head`` windows outstanding."""
        for h in range(self.n_heads) if heads is None else heads:
            while self.outstanding[h] < self.mix["queued_per_head"]:
                self._submit(h, now, now)

    # -- completion --------------------------------------------------------

    def _complete(self, h: int, k: int, status: str, out, now: float,
                  wings: Optional[tuple] = None) -> int:
        self.rec.got[(h, k)] = (now, status, served(out), wings)
        self.outstanding[h] -= 1
        return h

    def route(self, results, now: float) -> List[int]:
        """File each result with its head, by stream id; return the heads
        that completed a window (or a fused tick)."""
        done = []
        for r in results:
            h, session, wing = self.by_stream[r.stream_id]
            if session is None:
                done.append(self._complete(h, r.seq, r.status, r.result, now))
                continue
            self.wings[(h, r.seq, wing)] = served(r.result)
            session.absorb([r])
            for tick in session.drain():
                wings = (self.wings.pop((h, tick.seq, "event")),
                         self.wings.pop((h, tick.seq, "frame")))
                done.append(self._complete(h, tick.seq, tick.status,
                                           tick.result, now, wings))
        return done

    def busy(self) -> bool:
        return bool(self.eng.pending() or self.eng.in_flight)

    def step(self) -> None:
        with self.span("step"):
            t_a = time.perf_counter()
            out = self.eng.step()
            t_b = time.perf_counter()
        self.rec.steps.append((t_a, t_b))
        done = self.route(out, t_b)
        if self.closed and self.submitting and done:
            with self.span("submit"):
                self.top_up(t_b, set(done))

    # -- the loop ------------------------------------------------------------

    def serve_until(self, t_stop: float) -> None:
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                return
            if not self.closed:
                with self.span("submit"):
                    self.submit_due(now)
            if self.busy():
                self.step()
                continue
            wait = t_stop - now
            if not self.closed:
                wait = min(wait, self.next_due() - now)
            if wait > 0:
                with self.span("idle-wait"):
                    time.sleep(wait)

    def drain(self, t_w1: float) -> None:
        """Stop offering load, send what was due before the close, and
        step until every submitted window is back or ``DRAIN_S`` passes.
        A session holds a tick until both wings are in; nothing else can
        still be on its way once the engine is idle."""
        self.submitting = False
        if not self.closed:
            self.submit_due(time.perf_counter(), until=t_w1)
        limit = time.perf_counter() + DRAIN_S
        while self.busy() and time.perf_counter() < limit:
            self.step()

    def close(self) -> None:
        for h in self.heads:
            h.close()
        self.heads.clear()
        self.by_stream.clear()


def serve(server: Server, mix: dict, heads: int, seed: int, pool: tr.Pool,
          seconds: float, t_process: float, chips: int,
          trace_dir: Optional[str] = None) -> Record:
    """Warm traffic, the measured window (traced when ``trace_dir`` is
    given), the drain. ``t_process`` is the process start on the same
    clock: set-up runs from there to the start of the measured window.
    The device's peak memory is read before the heads are closed."""
    import jax
    rec = Record(seconds=seconds)
    spans = Spans(trace_dir is not None)
    load = Load(server, mix, heads, seed, pool, rec, spans)
    load.t0 = time.perf_counter()
    if load.closed:
        load.top_up(load.t0)
    load.serve_until(load.t0 + mix["warm_s"])
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    rec.t_w0 = time.perf_counter()
    rec.setup_s = rec.t_w0 - t_process
    rec.t_w1 = rec.t_w0 + seconds
    with spans(WINDOW_SPAN):
        load.serve_until(rec.t_w1)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    load.drain(rec.t_w1)
    rec.memory_peak_bytes = memory_peak(chips)
    load.close()
    return rec


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:chips]]
    return max(peaks) if peaks else 0
