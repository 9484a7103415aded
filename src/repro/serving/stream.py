"""Continuous batching over heterogeneous sensor streams.

The paper closes one loop: a single DVS camera feeding one 300 ms window
at a time into the SNE. A production deployment (many sensors / many
clients -- the ColibriUAV multi-sensor scenario, Ev-Edge's heterogeneous
event+frame workloads) must serve *many* concurrent streams across *both*
of Kraken's accelerator wings. :class:`StreamEngine` is the scheduler that
does this, and it is engine-agnostic: any
:class:`~repro.core.engine.InferenceEngine` (the event->SNN
:class:`~repro.core.pipeline.BatchedClosedLoop`, the frame->TCN
:class:`~repro.core.engine.FrameTCNEngine`, or a user-supplied engine)
plugs in unchanged.

Architecture:

  * streams declare a modality at ``submit`` (implicit when the engine
    set has exactly one); a stream is bound to its modality for life,
  * slots are partitioned per engine: each engine owns a fixed number of
    batch slots and runs ONE jit'd call per ``step()`` over its constant
    slot buffer -- a mixed event+frame step is exactly two jit'd calls,
  * per-stream FIFO window queues (``submit`` never blocks); windows
    within a stream are processed strictly in submission order, at most
    one in flight per stream per step, preserving closed-loop causality,
  * slot assignment is a pluggable :class:`SlotPolicy`:
    :class:`FairQuantumPolicy` (default) reproduces the
    fairness-quantum rotation -- a slot is pinned to a stream while it
    has queued windows and handed over when it drains, or after
    ``fair_quantum`` consecutive windows when other streams wait;
    :class:`DeadlinePolicy` adds earliest-deadline-first selection with
    aging, so urgent control loops preempt slack ones without starving
    anyone,
  * per-stream latency/energy accounting: every window gets its own
    Kraken breakdown (SNE wing: true event counts + firing rates; CUTIE
    wing: pixel counts + operand activity), bitwise identical to running
    that window alone through the single-window pipeline.

One-bin-width-per-engine contract: every window an engine serves shares
one ``duration_us`` (events are voxelized with one bin width; frames share
one tick period). Pin it with the ``duration_us`` constructor argument --
validated on every ``submit`` -- or leave it ``None`` to latch the first
submitted window's duration for the engine's lifetime. There is no reset:
construct a new engine (or pass a fresh ``engines=`` set) to change it.

Stateful streaming (``submit(..., stateful=True)``): the paper's SNN is
stateful across the control loop -- the LIF membranes integrate evidence
continuously -- yet a stateless server resets them at every window
boundary. A stream submitted with ``stateful=True`` instead carries its
engine state (the event wing: per-layer membrane planes) from window to
window: the lane keeps a slot-major state pytree next to its batch
slots, and on every dispatch each slot is fed the carry of the stream it
currently holds. State follows the STREAM, not the slot index: when the
policy moves a stream to another slot (rotation, deadline preemption) its
carry is gathered along; when a stream loses its slot its carry is
parked and re-attached on the next slot it wins. Parked carries live in
a per-lane device carry store, one home row per parked stream, and one
compiled program per step re-lays the slots and parks the carries of
streams that lost theirs (none when every carry stays in its row).
Slots are always
zeroed on admission -- a stream newly admitted into a slot previously
held by another (a "dirty" slot) starts from the cold-start state,
bitwise identical to a fresh B=1 run -- and stateless streams are fed
the zero state every window, so their results never depend on slot
history. ``reset_state(stream_id)`` zeroes a live stream's carry (the
gesture-boundary escape hatch) and ``retire(stream_id)`` drops a stream
and its state entirely. The state pytree is device-resident end to end:
in pipelined mode the carry chains dispatch-to-dispatch as jax
async-dispatch futures and never round-trips the host.

Session-handle API (the serving surface): a stream is opened, not
implied. ``StreamEngine.open(modality=..., stateful=..., deadline=...)``
returns a :class:`StreamHandle` that owns the stream's whole lifecycle:
``submit(window)`` queues work, ``reset_state()`` zeroes the carry,
``checkpoint()`` captures a host-serializable :class:`StreamCheckpoint`
(carry + queued windows + sequence position) that ``restore(ckpt)``
replays into a handle on a DIFFERENT engine process -- stream migration
-- and ``close()`` retires the stream. Modality and statefulness are
latched at ``open``; per-window metadata (deadlines) defaults to the
handle's and can be overridden per submit. The legacy id-keyed
``submit(stream_id, window, ...)`` form remains as a thin shim that
opens (or finds) the id's handle and forwards -- bitwise-identical
results -- while nudging callers to the handle API with a one-shot
``DeprecationWarning``. Cross-modal fusion (one sensor head driving BOTH
Kraken wings into a single actuation decision) binds one event handle
and one frame handle through :class:`~repro.serving.session.FusionSession`.

Fleet hooks (the control-plane surface ``repro.fleet`` drives): every
completed window feeds a sliding-horizon telemetry window on its
:class:`StreamStats` (``snapshot()`` freezes a consistent view with
derived rates -- windows/s, queue-depth p95, deadline-miss rate), and
``telemetry(modality)`` aggregates a whole lane into a
:class:`LaneTelemetry` row. Deadline-miss accounting interprets a finite
``deadline`` as an instant on the engine's ``deadline_clock`` (defaults
to ``time.perf_counter``; a fleet driver may install a shared logical
clock): a window collected after its deadline counts as missed.
``resize_lane`` changes a lane's slot count live -- kept streams stay
slotted, evicted streams rejoin the FRONT of the waiting line, carried
state is parked and re-attached, and the new batch size is pre-warmed
through the engines' per-``shape_key`` AOT caches so a resize costs one
warmed compile instead of a mid-serve stall. ``drain_lane`` collects
ONE lane's in-flight pipelined steps (other lanes stay dispatched),
which is what lets a stream checkpoint live without flushing the whole
engine.

Pipelining (``pipeline_depth >= 1``): ``step()`` dispatches each lane's
jit'd call asynchronously (no device sync on the critical path) and
returns the results of the step dispatched ``pipeline_depth`` steps ago,
so host-side window packing of step k+1 overlaps device compute of step
k. The emitted ``StreamResult`` sequence -- order and values -- is
bitwise identical to the synchronous engine; only *when* each result is
handed back (and therefore the wall-clock attribution) changes. Call
``flush()`` (or ``run()``, which drains automatically) to collect the
tail. Trade-off vs the synchronous default: windows are consumed from
their queues at dispatch, so a device-side failure surfaces at the later
collect, after the batch can no longer be retried by simply re-stepping.

Fault recovery (``EngineConfig.recovery``): with a
:class:`~repro.core._api.RecoveryConfig` attached, an engine failure is
a per-lane event, not an engine-wide crash:

  * a failed lane step is *retried* -- the synchronous two-phase
    dispatch leaves the failed lane's queues untouched, and a pipelined
    collect failure re-queues the poisoned records' windows at their
    seq positions with each stream's carry rolled back to its
    pre-window value -- after ``backoff_steps`` engine steps of lane
    cooldown (deterministic: backoff is counted in steps, not wall
    time);
  * a window failing ``max_retries`` times, or returning non-finite
    logits, is *quarantined*: moved to the lane's dead-letter queue,
    its ``StreamResult`` emitted with ``status="failed"``, the carry
    rolled back, the stream kept alive (subsequent windows chain from
    the pre-quarantine carry);
  * ``dead_after`` consecutive failed lane steps declare the lane
    *dead*: it stops calling its engine and fails queued windows fast
    (``status="failed"`` without touching the device), which keeps
    paired :class:`~repro.serving.session.FusionSession` ticks
    completing in degraded single-wing mode until
    ``replace_lane_engine`` installs a rebuilt engine (the
    :class:`~repro.fleet.supervisor.LaneSupervisor` automates rebuild +
    checkpoint-restore + replay).

Every retry/quarantine/dead transition is appended to
``StreamEngine.fault_log`` and counted on ``StreamStats`` /
:class:`LaneTelemetry`, so the fleet rebalancer scores unhealthy lanes.
With ``recovery=None`` (default) every failure path is bitwise-identical
to the pre-recovery engine: exceptions propagate, outputs are served
as-is.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import (Any, Callable, Deque, Dict, Hashable, List, Mapping,
                    Optional, Sequence, Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core._api import (EngineConfig, RecoveryConfig,
                             suppress_api_deprecations,
                             warn_deprecated_call)
from repro.core.energy import KrakenModel
from repro.core.engine import InferenceEngine
from repro.core.events import next_pow2
from repro.core.pipeline import (BatchedClosedLoop, ClosedLoopResult,
                                 _check_slot_divisible, export_state_slot,
                                 import_state_slot)
from repro.core.snn import SNNConfig

__all__ = ["StreamResult", "StreamStats", "StreamStatsSnapshot",
           "LaneTelemetry", "DeadLetter", "StreamEngine", "StreamHandle",
           "SlotPolicy", "FairQuantumPolicy", "DeadlinePolicy",
           "EngineConfig", "RecoveryConfig"]

# Distinguishes "kwarg not passed" from an explicit None in the legacy
# construction shim (an explicitly-passed legacy kwarg must both warn
# and win over the EngineConfig default).
_UNSET_KW = object()


@dataclasses.dataclass
class StreamResult:
    """One served window: which stream, which window index, and the
    closed-loop outcome (prediction, PWM, latency/energy breakdown).

    ``status`` is ``"ok"`` for a normally served window. Under fault
    recovery a quarantined or dead-lane-failed window is still emitted
    -- closed-loop callers need to know the tick happened -- with
    ``status="failed"``, ``result=None`` and the failure reason in
    ``error``; :class:`~repro.serving.session.FusionSession` emits
    ``status="degraded"`` ticks when one wing failed.
    """

    stream_id: Hashable
    seq: int                      # submission-time sequence number
    result: Optional[ClosedLoopResult]
    modality: str = "event"
    status: str = "ok"            # "ok" | "failed" | "degraded"
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.status == "ok"


@dataclasses.dataclass(frozen=True)
class DeadLetter:
    """One quarantined window, parked on its lane's dead-letter queue:
    enough to re-submit it by hand (the window itself, its stream and
    sequence position) plus why it was poisoned."""

    stream_id: Hashable
    seq: int
    modality: str
    item: Any
    deadline: Optional[float]
    error: str


@dataclasses.dataclass(frozen=True)
class StreamStatsSnapshot:
    """A frozen, host-side view of one stream's accounting.

    The autoscaler/rebalancer read THIS, not the live mutable counters:
    every derived rate inside is computed from one consistent point in
    time. Cumulative fields mirror :class:`StreamStats`; the
    ``horizon_*`` fields and derived rates cover only the last
    ``horizon`` completions (the sliding telemetry window), so a stream
    that was hot an hour ago but idle now scores idle.
    """

    windows: int
    queued: int
    energy_mj: float
    mean_latency_ms: float
    realtime_fraction: float
    deadline_windows: int         # completed windows that carried a deadline
    deadline_missed: int          # ... collected after their deadline
    horizon: int                  # completions the sliding fields cover (max)
    horizon_windows: int          # completions actually in the window
    horizon_deadline_windows: int
    horizon_missed: int
    windows_per_s: float          # completion rate over the sliding window
    queue_depth_p95: float        # p95 of at-completion queue depths
    deadline_miss_rate: float     # horizon_missed / horizon_deadline_windows
    retries: int = 0              # failed dispatch/collect attempts
    quarantined: int = 0          # windows moved to the dead-letter queue
    fusion_ticks: int = 0         # paired-stream ticks observed at dispatch
    fusion_ticks_paired: int = 0  # ... whose wings shared one engine step
    paired_tick_rate: float = 1.0  # paired / observed (1.0 when unpaired)


@dataclasses.dataclass
class StreamStats:
    """Per-stream accounting, accumulated as windows complete.

    Besides the cumulative counters, every completion is sampled into a
    bounded sliding window (``horizon`` most recent completions: wall
    time, queue depth left behind, deadline outcome) so
    :meth:`snapshot` can derive recent rates -- windows/s, queue-depth
    p95, deadline-miss rate -- without unbounded history.
    """

    windows: int = 0
    energy_mj: float = 0.0
    latency_ms_sum: float = 0.0
    realtime_windows: int = 0
    queued: int = 0               # still waiting in this stream's queue
    deadline_windows: int = 0     # completed windows that had a deadline
    deadline_missed: int = 0      # ... that completed past it
    retries: int = 0              # failed attempts charged to this stream
    quarantined: int = 0          # windows dead-lettered
    fusion_ticks: int = 0         # ticks of a paired (fusion) stream seen
    fusion_ticks_paired: int = 0  # ... both wings dispatched the same step
    horizon: int = 64             # sliding-window length (completions)
    samples: Deque = dataclasses.field(default_factory=deque, repr=False)

    def __post_init__(self):
        self.samples = deque(self.samples, maxlen=self.horizon)

    def note_completion(self, wall_t: float, queue_depth: int,
                        missed: Optional[bool]) -> None:
        """Record one completed window: wall-clock instant, the queue
        depth it left behind, and its deadline outcome (``None`` = the
        window carried no deadline)."""
        if missed is not None:
            self.deadline_windows += 1
            if missed:
                self.deadline_missed += 1
        self.samples.append((wall_t, queue_depth, missed))

    @property
    def mean_latency_ms(self) -> float:
        return self.latency_ms_sum / self.windows if self.windows else 0.0

    @property
    def realtime_fraction(self) -> float:
        return self.realtime_windows / self.windows if self.windows else 0.0

    @property
    def mean_power_mw(self) -> float:
        """Average power while processing (energy over busy time)."""
        return (self.energy_mj / (self.latency_ms_sum * 1e-3)
                if self.latency_ms_sum else 0.0)

    def snapshot(self) -> StreamStatsSnapshot:
        """Freeze a consistent view with derived sliding-horizon rates."""
        samples = list(self.samples)
        n = len(samples)
        span = samples[-1][0] - samples[0][0] if n >= 2 else 0.0
        wps = (n - 1) / span if span > 0.0 else 0.0
        depths = sorted(s[1] for s in samples)
        p95 = (float(depths[max(0, math.ceil(0.95 * n) - 1)])
               if depths else 0.0)
        dated = [s[2] for s in samples if s[2] is not None]
        missed = sum(1 for m in dated if m)
        return StreamStatsSnapshot(
            windows=self.windows, queued=self.queued,
            energy_mj=self.energy_mj,
            mean_latency_ms=self.mean_latency_ms,
            realtime_fraction=self.realtime_fraction,
            deadline_windows=self.deadline_windows,
            deadline_missed=self.deadline_missed,
            horizon=self.horizon, horizon_windows=n,
            horizon_deadline_windows=len(dated), horizon_missed=missed,
            windows_per_s=wps, queue_depth_p95=p95,
            deadline_miss_rate=missed / len(dated) if dated else 0.0,
            retries=self.retries, quarantined=self.quarantined,
            fusion_ticks=self.fusion_ticks,
            fusion_ticks_paired=self.fusion_ticks_paired,
            paired_tick_rate=(self.fusion_ticks_paired / self.fusion_ticks
                              if self.fusion_ticks else 1.0))


@dataclasses.dataclass(frozen=True)
class LaneTelemetry:
    """One engine lane, aggregated for the fleet control plane.

    ``backlog_per_slot`` is the autoscaler's grow signal (queued windows
    per batch slot); ``deadline_miss_rate`` pools every stream's sliding
    horizon (missed / with-deadline completions), so it reacts to recent
    pressure, not lifetime averages. ``streams`` holds the consistent
    per-stream :class:`StreamStatsSnapshot` rows the aggregate was
    computed from.
    """

    modality: str
    slots: int
    occupied: int                 # slots currently pinned to a stream
    waiting: int                  # streams in the waiting line
    queued: int                   # windows queued across the lane
    in_flight: int                # dispatched-but-uncollected windows
    windows: int                  # completed windows (cumulative)
    windows_per_s: float          # summed sliding-horizon completion rate
    deadline_miss_rate: float     # pooled over the streams' horizons
    streams: Dict[Hashable, StreamStatsSnapshot] = dataclasses.field(
        default_factory=dict)
    retries: int = 0              # cumulative failed attempts on the lane
    quarantined: int = 0          # cumulative dead-lettered windows
    dead: bool = False            # lane declared dead (fail-fast mode)
    paired_tick_rate: float = 1.0  # fusion ticks co-scheduled, pooled
                                   # over the lane's paired streams

    @property
    def fault_rate(self) -> float:
        """Retries + quarantines per completed-or-quarantined window;
        the rebalancer's unhealthiness signal."""
        denom = self.windows + self.quarantined
        return ((self.retries + self.quarantined) / denom
                if denom else 0.0)

    @property
    def backlog_per_slot(self) -> float:
        return self.queued / self.slots if self.slots else 0.0

    @property
    def occupancy(self) -> float:
        return self.occupied / self.slots if self.slots else 0.0


class _FreeSlot:
    """Sentinel for an unassigned batch slot (distinct from any stream id,
    including ``None``, which is a legal Hashable stream id)."""

    def __repr__(self):
        return "<free slot>"


_FREE = _FreeSlot()


@dataclasses.dataclass
class _Queued:
    """One queued submission: the item plus its submission-time metadata."""

    item: Any
    seq: int
    deadline: Optional[float] = None


@dataclasses.dataclass
class _InflightLane:
    """One lane's share of a dispatched (not yet collected) step.

    ``entries`` is slot-aligned: ``(stream_id, seq)`` per served slot,
    ``None`` per empty one. ``kind`` says what ``pending`` holds:
    ``"results"`` -- the finished per-slot results (synchronous mode,
    where infer completes before any queue state moves -- the retry-safe
    path); ``"handle"`` -- the engine's opaque async-dispatch handle;
    ``"batch"`` -- a prepared batch for an engine without the async
    split, inferred (synchronously) at collect time.

    Recovery bookkeeping (populated only when the engine has a
    :class:`~repro.core._api.RecoveryConfig`): ``items`` keeps the
    popped :class:`_Queued` objects slot-aligned so a failed record can
    re-queue its windows under their original sequence numbers;
    ``prev_carry`` maps each dispatched stateful stream to where its
    PRE-window carry sits, ``(state fed in, row)``: the value quarantine
    rolls back to.

    ``step`` is the number of the ``step()`` call that dispatched the
    record, so that its collect -- a later call's, when pipelined -- is
    traced under the same step id.
    """

    lane: "EngineLane"
    key: Hashable
    entries: List[Optional[tuple]]
    kind: str
    pending: Any
    items: Optional[List[Optional["_Queued"]]] = None
    prev_carry: Optional[Dict[Hashable, Any]] = None
    step: int = -1


@dataclasses.dataclass
class EngineLane:
    """One engine's scheduling state: its slots, queues, and waiting line.

    This is the view a :class:`SlotPolicy` operates on. Slots hold stream
    ids (or the free sentinel); ``queues`` maps every stream of this
    modality to its FIFO of :class:`_Queued` entries; ``waiting`` holds
    streams without a slot, in arrival order.

    Carried-state fields (engines exposing ``init_state``):
    ``state`` is the slot-major device pytree fed to the NEXT dispatch
    row-aligned with ``slots`` at that dispatch; ``state_streams`` tracks,
    per row, which stateful stream's carry the row holds (rows of
    stateless or free slots are dead and zeroed on reuse); ``store`` is
    the device carry store, the engine's state pytree with
    ``capacity + 1`` rows, whose last row stays the cold-start state;
    ``parked`` maps each stateful stream that currently has no slot to
    the home row of ``store`` holding its carry; ``stateful`` is the set
    of streams that opted into carry at submit. ``capacity`` is the
    next power of two at or above the lane's stateful streams (it only
    grows, by doubling), and ``move_exe`` caches the compiled
    state-move program per ``(slots, capacity)``.
    Invariant: a stateful stream's carry lives in exactly one of a state
    row or ``parked`` (or nowhere, meaning cold start).
    """

    modality: str
    engine: InferenceEngine
    slots: List[Hashable]
    slot_runs: List[int]
    waiting: Deque[Hashable]
    queues: Dict[Hashable, Deque[_Queued]]
    shape_keys: set
    supports_state: bool = False
    stateful: set = dataclasses.field(default_factory=set)
    state: Any = None
    state_streams: List[Hashable] = dataclasses.field(default_factory=list)
    parked: Dict[Hashable, int] = dataclasses.field(default_factory=dict)
    zero_state: Any = None
    store: Any = None
    capacity: int = 0
    move_exe: Dict[tuple, Callable] = dataclasses.field(
        default_factory=dict)
    # Fault-recovery state (only ever mutated when the engine carries a
    # RecoveryConfig; all-defaults otherwise).
    dead: bool = False            # fail-fast mode until engine replaced
    fail_streak: int = 0          # consecutive failed lane steps
    cooldown: int = 0             # backoff steps left before redispatch
    retries: Dict[tuple, int] = dataclasses.field(default_factory=dict)
    dead_letter: Deque = dataclasses.field(default_factory=deque)
    n_retries: int = 0            # cumulative, for telemetry
    n_quarantined: int = 0

    def pending(self) -> int:
        return sum(len(q) for q in self.queues.values())


# ----------------------------------------------------------------------
# Slot policies.
# ----------------------------------------------------------------------

class SlotPolicy:
    """Decides which streams hold an engine's batch slots each step.

    ``assign(lane)`` runs once per lane per step, before the batch is
    gathered: it frees slots (drained or rotated streams) and fills free
    slots from the waiting line. Policies must keep the invariant that a
    schedulable stream is tracked by exactly one of: a held slot or a
    waiting-line entry.

    Policies keeping per-stream bookkeeping (aging counters, histories)
    should additionally implement ``forget(stream_id)`` -- the engine
    calls it when a stream is retired, so a later stream reusing the id
    cannot inherit the old stream's bookkeeping. The hook is duck-typed
    (probed with ``getattr``), like the engines' optional extensions.
    """

    def assign(self, lane: EngineLane) -> None:
        raise NotImplementedError


class FairQuantumPolicy(SlotPolicy):
    """The default: pin-until-drained with a fairness quantum.

    A slot stays pinned to its stream while the stream has queued windows;
    it is handed to the next waiting stream the moment the stream drains,
    or after ``fair_quantum`` consecutive windows when other streams are
    waiting (the pinned stream is rotated to the back of the waiting
    line). Free slots are filled in arrival order. No stream starves
    under continuous submission.
    """

    def __init__(self, fair_quantum: int = 4):
        if fair_quantum < 1:
            raise ValueError(
                f"fair_quantum must be >= 1, got {fair_quantum}")
        self.fair_quantum = fair_quantum

    def assign(self, lane: EngineLane) -> None:
        contended = any(lane.queues[s] for s in lane.waiting)
        for i, sid in enumerate(lane.slots):
            if sid is _FREE:
                continue
            if not lane.queues[sid]:
                lane.slots[i] = _FREE
                lane.slot_runs[i] = 0
            elif contended and lane.slot_runs[i] >= self.fair_quantum:
                # Rotate: back of the waiting line, slot to the next stream.
                lane.waiting.append(sid)
                lane.slots[i] = _FREE
                lane.slot_runs[i] = 0
        self._note_round(lane)
        for i, sid in enumerate(lane.slots):
            if sid is _FREE:
                cand = self._take(lane)
                if cand is None:
                    break   # no more waiting work
                lane.slots[i] = cand
                lane.slot_runs[i] = 0

    def _note_round(self, lane: EngineLane) -> None:
        """Hook: called once per assign round, after rotation, before any
        slot is filled. Subclasses may update per-round bookkeeping."""

    def _take(self, lane: EngineLane) -> Optional[Hashable]:
        """Pop the next waiting stream with queued work (arrival order);
        drained waiting entries are discarded as encountered (they re-enter
        on their next submit)."""
        while lane.waiting:
            cand = lane.waiting.popleft()
            if lane.queues[cand]:
                return cand
        return None


class DeadlinePolicy(FairQuantumPolicy):
    """Deadline/priority-aware slot assignment (EDF + aging + wait bound).

    Streams submit windows with an optional ``deadline`` (any consistent
    unit -- e.g. control-tick index or wall milliseconds; smaller = more
    urgent; ``None`` = slack). Free slots go to the waiting stream whose
    head window has the earliest *effective* deadline:

        effective = deadline - aging * rounds_passed_over

    with ``None`` sorting after every finite deadline. Aging bounds the
    lateness of finite-deadline streams, but cannot by itself protect an
    undeadlined stream from a continuous feed of urgent work -- so the
    policy additionally enforces a hard anti-starvation bound: a live
    waiting stream passed over ``max_wait`` times is served next
    regardless of deadlines. Together with the inherited fairness quantum
    (which bounds how long a pinned stream may hold a slot while others
    wait), every live stream is guaranteed a slot within
    ``O(max_wait * fair_quantum)`` engine steps.
    """

    _NO_DEADLINE = math.inf

    def __init__(self, fair_quantum: int = 4, *, aging: float = 1.0,
                 max_wait: int = 16):
        super().__init__(fair_quantum)
        if aging < 0:
            raise ValueError(f"aging must be >= 0, got {aging}")
        if max_wait < 1:
            raise ValueError(f"max_wait must be >= 1, got {max_wait}")
        self.aging = aging
        self.max_wait = max_wait
        self._waited: Dict[Hashable, int] = {}

    def _note_round(self, lane: EngineLane) -> None:
        """Once per scheduling round: discard drained waiting entries
        (they re-enter on their next submit, exactly as the base policy
        discards them lazily) and age every live waiting stream by one
        round -- regardless of how many free slots this round fills."""
        live = [sid for sid in lane.waiting if lane.queues[sid]]
        if len(live) != len(lane.waiting):
            dropped = set(lane.waiting) - set(live)
            lane.waiting.clear()
            lane.waiting.extend(live)
            for sid in dropped:
                self._waited.pop(sid, None)
        for sid in live:
            self._waited[sid] = self._waited.get(sid, 0) + 1

    def _take(self, lane: EngineLane) -> Optional[Hashable]:
        best = None
        best_key = None
        for pos, sid in enumerate(lane.waiting):
            if not lane.queues[sid]:
                continue        # submitted mid-round; picked next round
            waited = self._waited.get(sid, 0)
            if waited >= self.max_wait:
                # Hard bound: the longest-passed-over stream goes first.
                key = (-1, -waited, pos)
            else:
                head = lane.queues[sid][0].deadline
                base = self._NO_DEADLINE if head is None else head
                key = (0, base - self.aging * waited, pos)
            if best is None or key < best_key:
                best, best_key = sid, key
        if best is None:
            return None
        lane.waiting.remove(best)
        self._waited.pop(best, None)
        return best

    def forget(self, stream_id: Hashable) -> None:
        """Drop the stream's aging counter (engine calls this on retire
        so a reused id starts with fresh aging)."""
        self._waited.pop(stream_id, None)


def _pack(lane: "EngineLane", heads: List):
    """The lane's engine packs one head per slot into its fixed batch,
    traced as a ``pack`` span whose value is the bytes of the numpy
    arrays the batch holds (0 for a batch that is not an object of
    arrays)."""
    with tracing.span("pack", lane=lane.modality) as span:
        batch = lane.engine.prepare(heads, batch_size=len(lane.slots))
        span.value = sum(
            a.nbytes for a in getattr(batch, "__dict__", {}).values()
            if isinstance(a, np.ndarray))
    return batch


def _move_carries(state, store, idx):
    """The state-move program, over flat lists of state leaves: each
    ``state`` leaf has one row per slot, each ``store`` leaf
    ``capacity + 1`` rows. ``idx`` is int32 ``(3, slots)``: row 0 says
    where each slot's carry comes from, as a row of ``concat(state,
    store)`` (a buffer row, a home row or the cold-start row); rows 1
    and 2 pair the buffer rows of streams that lost their slot with the
    home rows they park in, padded with home rows past the store, which
    the scatter drops. Returns ``(state_in, store')``; every row is an
    exact copy."""
    state_in, stored = [], []
    for a, s in zip(state, store):
        state_in.append(jnp.take(jnp.concatenate([a, s]), idx[0], axis=0,
                                 mode="clip"))
        stored.append(s.at[idx[2]].set(a[idx[1]], mode="drop"))
    return state_in, stored


def _free_homes(lane: "EngineLane"):
    """The store's home rows no parked stream holds, in order. There is
    one for each stateful stream not parked, since the store has a row
    for every stateful stream of the lane."""
    held = set(lane.parked.values())
    return (r for r in range(lane.capacity) if r not in held)


# ----------------------------------------------------------------------
# The session-handle serving surface.
# ----------------------------------------------------------------------

def _export_carry(engine: InferenceEngine, state, slot: int):
    """One slot's carry as a host pytree, via the engine's duck-typed
    ``export_state`` (falling back to the generic leading-axis slice
    for engines that do not implement it)."""
    export = getattr(engine, "export_state", export_state_slot)
    return export(state, slot)


def _import_carry(engine: InferenceEngine, payload):
    """An exported carry back on device as a 1-slot state (row 0), via
    the engine's duck-typed ``import_state`` splicing into a fresh
    1-slot zero state."""
    import_ = getattr(engine, "import_state", import_state_slot)
    return import_(engine.init_state(1), 0, payload)


class StreamHandle:
    """One stream's lifecycle, owned: the object ``StreamEngine.open``
    returns and the primary serving surface.

    A handle latches its stream's identity for life -- modality (which
    engine lane serves it), statefulness (whether engine state carries
    across its windows), and a default ``deadline`` for deadline-aware
    slot policies. Everything a caller does to a stream goes through its
    handle:

      * ``submit(window[, deadline=...])`` -- queue one window; returns
        the per-stream sequence number later reported by
        ``StreamResult.seq``. Never blocks.
      * ``reset_state()`` -- zero the carried state (gesture boundary);
        the next dispatched window starts cold.
      * ``checkpoint()`` -- capture the stream as a host-serializable
        :class:`~repro.serving.session.StreamCheckpoint`: the carried
        state (exported through the engine's duck-typed
        ``export_state``), any still-queued windows, and the sequence
        position. Requires no windows in flight (``flush()`` first).
      * ``restore(ckpt)`` -- replay a checkpoint into THIS handle (which
        must be fresh): the carry is imported and parked until the
        stream wins a slot, queued windows are re-queued under their
        original sequence numbers, and numbering resumes -- results
        after migration are bitwise identical to the uninterrupted run.
      * ``close()`` -- retire the stream: queue, slot, waiting entry and
        carry are dropped (idempotent; returns discarded window count).

    Handles do not collect results -- ``step()``/``run()``/``flush()``
    on the engine remain the completion surface, emitting
    :class:`StreamResult` rows for every open stream.
    """

    def __init__(self, engine: "StreamEngine", lane: EngineLane,
                 stream_id: Hashable, stateful: bool,
                 deadline: Optional[float]):
        self._engine = engine
        self._lane = lane
        self.stream_id = stream_id
        self.stateful = bool(stateful)
        self.deadline = deadline
        self.closed = False

    def __repr__(self):
        state = "closed" if self.closed else "open"
        return (f"<StreamHandle {self.stream_id!r} {self._lane.modality} "
                f"stateful={self.stateful} {state}>")

    @property
    def modality(self) -> str:
        return self._lane.modality

    @property
    def engine(self) -> "StreamEngine":
        """The owning engine (the completion surface for this stream's
        results, and the lane-level control surface the fleet drives)."""
        return self._engine

    @property
    def stats(self) -> StreamStats:
        """This stream's accumulated accounting."""
        return self._engine.stream_stats[self.stream_id]

    @property
    def queued(self) -> int:
        """Windows still waiting in this stream's queue."""
        return 0 if self.closed else len(self._lane.queues[self.stream_id])

    @property
    def next_seq(self) -> int:
        """The sequence number the next ``submit`` will return."""
        self._check_open()
        return self._engine._seq[self.stream_id]

    def _check_open(self) -> None:
        if self.closed:
            raise ValueError(
                f"handle for stream {self.stream_id!r} is closed")

    def _check_not_inflight(self, verb: str) -> None:
        for step_recs in self._engine._inflight:
            for rec in step_recs:
                for entry in rec.entries:
                    if entry is not None and entry[0] == self.stream_id:
                        raise ValueError(
                            f"stream {self.stream_id!r} has in-flight "
                            f"windows; flush() before {verb}")

    # -- submission ------------------------------------------------------

    def validate(self, window: Any) -> None:
        """Check ``window`` against this stream's engine without queueing
        it (raises exactly what ``submit`` would). Lets a caller
        coordinating multiple handles (e.g. a FusionSession tick)
        validate every window BEFORE queueing any, keeping the group
        submit atomic."""
        self._check_open()
        self._lane.engine.validate(window)

    def submit(self, window: Any, *,
               deadline: Optional[float] = None) -> int:
        """Queue one window; returns its per-stream sequence number.

        ``deadline`` overrides the handle's default for this window
        (consumed by deadline-aware policies; smaller = more urgent).
        The window is validated by the engine BEFORE any queue state
        moves, so a rejected submit burns no sequence number.
        """
        self._check_open()
        lane, sid, eng = self._lane, self.stream_id, self._engine
        lane.engine.validate(window)
        seq = eng._seq[sid]
        eng._seq[sid] = seq + 1
        lane.queues[sid].append(_Queued(
            window, seq, self.deadline if deadline is None else deadline))
        # A stream is schedulable via exactly one of: a held slot or a
        # waiting-line entry (covers streams that drained and come back).
        if sid not in lane.slots and sid not in lane.waiting:
            lane.waiting.append(sid)
        eng.stream_stats[sid].queued += 1
        return seq

    # -- carried state ---------------------------------------------------

    def reset_state(self) -> None:
        """Zero the carried state without retiring the stream -- the
        gesture-boundary escape hatch. Applies from the next dispatch;
        windows already in flight keep the old carry."""
        self._check_open()
        lane, sid = self._lane, self.stream_id
        if not self.stateful:
            raise ValueError(f"stream {sid!r} is not stateful")
        lane.parked.pop(sid, None)
        for j, owner in enumerate(lane.state_streams):
            if owner is not _FREE and owner == sid:
                lane.state_streams[j] = _FREE

    def checkpoint(self):
        """Capture this stream for migration: carried state (host
        numpy), still-queued windows, and the sequence position, as a
        :class:`~repro.serving.session.StreamCheckpoint`.

        The engine keeps serving the stream afterwards -- a checkpoint
        is a copy, not a detach. Raises while windows are in flight
        (their state commits have not landed yet; ``flush()`` first).
        """
        self._check_open()
        self._check_not_inflight("checkpointing")
        from repro.serving.session import StreamCheckpoint
        lane, sid = self._lane, self.stream_id
        payload = None
        if self.stateful:
            row = next((j for j, owner in enumerate(lane.state_streams)
                        if owner is not _FREE and owner == sid), None)
            if row is not None:
                payload = _export_carry(lane.engine, lane.state, row)
            elif sid in lane.parked:
                payload = _export_carry(lane.engine, lane.store,
                                        lane.parked[sid])
            # else: cold start -- a None payload restores to zero state.
        return StreamCheckpoint(
            stream_id=sid, modality=lane.modality, stateful=self.stateful,
            next_seq=self._engine._seq[sid],
            duration_us=lane.engine.duration_us, state=payload,
            deadline=self.deadline,
            queued=tuple((q.item, q.seq, q.deadline)
                         for q in lane.queues[sid]))

    def restore(self, ckpt) -> "StreamHandle":
        """Replay ``ckpt`` into this handle; returns the handle.

        The handle must be fresh (nothing submitted, no carry) and match
        the checkpoint's modality and statefulness; the lane's engine
        must agree on ``duration_us`` (an unlatched engine latches the
        checkpoint's). Remaining windows then continue bitwise-identical
        to the uninterrupted run on the original engine.
        """
        self._check_open()
        lane, sid, eng = self._lane, self.stream_id, self._engine
        if (eng._seq[sid] != 0 or lane.queues[sid] or sid in lane.parked
                or any(o is not _FREE and o == sid
                       for o in lane.state_streams)):
            raise ValueError(
                f"restore needs a fresh handle; stream {sid!r} already "
                f"has submitted windows or a carry")
        if ckpt.modality != lane.modality:
            raise ValueError(
                f"checkpoint is {ckpt.modality!r}, handle is bound to "
                f"{lane.modality!r}")
        if bool(ckpt.stateful) != self.stateful:
            raise ValueError(
                f"checkpoint stateful={ckpt.stateful} != handle "
                f"stateful={self.stateful}; open the handle to match")
        # Re-queued windows get the same validate-before-any-state-moves
        # treatment as submit(): an engine that cannot serve them (e.g.
        # different frame geometry) rejects the restore here, not later
        # mid-dispatch. Validation may latch an unlatched engine's
        # duration; roll that back too if anything rejects, so a failed
        # restore leaves the engine exactly as it found it.
        prev_duration = lane.engine.duration_us
        try:
            if ckpt.duration_us is not None:
                if lane.engine.duration_us is None:
                    lane.engine.duration_us = ckpt.duration_us
                elif lane.engine.duration_us != ckpt.duration_us:
                    raise ValueError(
                        f"checkpoint duration_us={ckpt.duration_us} != "
                        f"engine duration_us={lane.engine.duration_us}")
            for item, _seq, _deadline in ckpt.queued:
                lane.engine.validate(item)
        except Exception:
            lane.engine.duration_us = prev_duration
            raise
        if ckpt.state is not None:
            eng._park_rows(lane, _import_carry(lane.engine, ckpt.state),
                           [(sid, 0)])
        eng._seq[sid] = int(ckpt.next_seq)
        if self.deadline is None:
            self.deadline = ckpt.deadline
        for item, seq, deadline in ckpt.queued:
            lane.queues[sid].append(_Queued(item, seq, deadline))
            eng.stream_stats[sid].queued += 1
        if lane.queues[sid] and sid not in lane.slots \
                and sid not in lane.waiting:
            lane.waiting.append(sid)
        return self

    # -- retirement ------------------------------------------------------

    def close(self) -> int:
        """Retire the stream entirely: queue, slot, waiting entry, and
        carried state. Returns the number of queued windows discarded
        (idempotent: closing a closed handle returns 0).

        The slot it held is freed with its buffers dead: the next stream
        admitted there starts from the zero state. Closing with windows
        in flight (pipelined) discards exactly this stream's in-flight
        records -- their results are never emitted and count toward the
        returned discard total -- while lane-mates sharing the
        dispatched steps stay in flight untouched.
        ``stream_stats`` keeps the history until the id is reused; a
        later ``open`` with the same id is a brand-new stream (fresh seq
        numbering, fresh state).
        """
        if self.closed:
            return 0
        lane, sid, eng = self._lane, self.stream_id, self._engine
        # Scrub this stream out of any dispatched-but-uncollected step:
        # the slot's device compute still runs, but its result slot is
        # orphaned (skipped at collect). Lane-mates are untouched.
        dropped = 0
        for step_recs in self._engine._inflight:
            for rec in step_recs:
                if rec.lane is not lane:
                    continue
                for i, entry in enumerate(rec.entries):
                    if entry is not None and entry[0] == sid:
                        rec.entries[i] = None
                        if rec.items is not None:
                            rec.items[i] = None
                        dropped += 1
        queued_dropped = len(lane.queues.pop(sid))
        dropped += queued_dropped
        if sid in lane.waiting:
            lane.waiting.remove(sid)
        for i, owner in enumerate(lane.slots):
            if owner is not _FREE and owner == sid:
                lane.slots[i] = _FREE
                lane.slot_runs[i] = 0
        for j, owner in enumerate(lane.state_streams):
            if owner is not _FREE and owner == sid:
                lane.state_streams[j] = _FREE
        lane.parked.pop(sid, None)
        lane.stateful.discard(sid)
        for key in [k for k in lane.retries if k[0] == sid]:
            del lane.retries[key]
        eng.unpair_streams(sid)
        del eng._stream_lane[sid]
        eng._seq.pop(sid, None)
        eng._handles.pop(sid, None)
        # In-flight scrubs were already uncounted from the queued stat
        # at dispatch; only the still-queued windows adjust it here.
        eng.stream_stats[sid].queued -= queued_dropped
        # Policies with per-stream bookkeeping (e.g. DeadlinePolicy's
        # aging counters) drop it via the duck-typed forget hook, so a
        # reused id cannot inherit the retired stream's state.
        forget = getattr(eng.policy, "forget", None)
        if forget is not None:
            forget(sid)
        self.closed = True
        return dropped


# ----------------------------------------------------------------------
# The engine-agnostic streaming scheduler.
# ----------------------------------------------------------------------

class StreamEngine:
    """Continuous batching of sensor windows over per-engine batch slots.

    The serving surface is the session-handle API:
    ``open(modality=..., stateful=..., deadline=...)`` returns a
    :class:`StreamHandle` owning one stream's lifecycle (``submit`` /
    ``reset_state`` / ``checkpoint`` / ``restore`` / ``close``);
    ``step()`` / ``run()`` / ``flush()`` emit completed
    :class:`StreamResult` rows across all open streams. The legacy
    id-keyed ``submit(stream_id, ...)`` form is a thin shim over
    handles -- bitwise-identical scheduling and results -- kept for
    pre-session callers (it warns once per engine).

    Construction is unified behind :class:`~repro.core._api.
    EngineConfig` -- everything that shapes the engine (slots, policy,
    pipelining, kernel fusion, the device mesh) is one frozen value:

      * ``StreamEngine(params, cfg, EngineConfig(max_streams=8))`` --
        builds one :class:`~repro.core.pipeline.BatchedClosedLoop`
        internally; a bare ``StreamEngine(params, cfg)`` uses the
        default config,
      * ``StreamEngine(engines=[event_engine, frame_engine],
        config=...)`` -- heterogeneous form: any set of
        :class:`~repro.core.engine.InferenceEngine` objects, one lane
        (slot partition + jit'd call per step) per engine, keyed by each
        engine's declared ``modality``.

    The pre-config kwarg spellings (``max_streams=``, ``policy=``,
    ``pipeline_depth=``, ...) still work as a shim that builds the same
    ``EngineConfig`` internally -- bitwise-identical engines -- and
    announces the migration once per engine. ``config=`` and legacy
    kwargs are mutually exclusive.

    ``max_streams`` is the slot count per engine (or a
    ``{modality: count}`` mapping). ``duration_us`` pins the
    one-bin-width-per-engine contract up front (validated on every
    submit); ``None`` latches each engine's first submitted duration.

    ``config.mesh`` shards every lane's slot axis across the mesh's
    data axis: one collective-free jit'd step per lane spanning all
    devices, bitwise-identical to the single-device engine (see
    ``repro.distributed.make_mesh``). Slot gathers, parking, and
    reassignment stay host-side row splices exactly as on one device --
    the resharding ``device_put`` inside each engine's dispatch is the
    only cross-device movement. Every lane's slot count must divide by
    the mesh's slot-axis size; caller-provided engines are attached via
    their ``attach_mesh`` (an engine already pinned to a different mesh
    is rejected).
    """

    def __init__(
        self,
        params=None,
        cfg: Optional[SNNConfig] = None,
        config: Optional[EngineConfig] = None,
        *,
        engines: Union[None, InferenceEngine,
                       Sequence[InferenceEngine],
                       Mapping[str, InferenceEngine]] = None,
        model: Optional[KrakenModel] = None,
        lif_scan_fn: Optional[Callable] = None,
        max_streams=_UNSET_KW,
        fair_quantum=_UNSET_KW,
        policy=_UNSET_KW,
        duration_us=_UNSET_KW,
        window_ms=_UNSET_KW,
        fuse_fc=_UNSET_KW,
        pipeline_depth=_UNSET_KW,
    ):
        legacy = {k: v for k, v in dict(
            max_streams=max_streams, fair_quantum=fair_quantum,
            policy=policy, duration_us=duration_us, window_ms=window_ms,
            fuse_fc=fuse_fc, pipeline_depth=pipeline_depth,
        ).items() if v is not _UNSET_KW}
        if config is not None:
            if not isinstance(config, EngineConfig):
                raise TypeError(
                    f"config must be an EngineConfig, got "
                    f"{type(config).__name__}")
            if legacy:
                raise ValueError(
                    f"config= and legacy construction kwargs are "
                    f"mutually exclusive (got both config= and "
                    f"{sorted(legacy)}); fold the kwargs into the "
                    f"EngineConfig")
        else:
            if legacy:
                warn_deprecated_call(
                    self, "kwargs-construction",
                    "StreamEngine construction kwargs (max_streams=, "
                    "policy=, pipeline_depth=, ...) are a legacy "
                    "spelling; pass one EngineConfig instead: "
                    "StreamEngine(params, cfg, EngineConfig(...)) / "
                    "StreamEngine(engines=..., config=EngineConfig(...))")
            config = EngineConfig(**legacy)
        self.config = config
        self.mesh = config.mesh
        self.pipeline_depth = config.pipeline_depth
        self.recovery: Optional[RecoveryConfig] = config.recovery
        # Chronological record of every fault-recovery transition:
        # {"step", "kind": "retry"|"quarantine"|"lane_dead"|"requeue"|
        #  "lane_replaced", "modality", "stream", "seq", "error"}.
        # Feeds the chaos-soak assertions and the bench recovery metric.
        self.fault_log: List[dict] = []
        # Failed StreamResults produced during dispatch (sync retry
        # exhaustion, dead-lane fail-fast); drained into step() output.
        self._pending_failures: List[StreamResult] = []
        self._inflight: Deque[List[_InflightLane]] = deque()
        if engines is None:
            if params is None or cfg is None:
                raise ValueError("give (params, cfg) or engines=")
            engines = [BatchedClosedLoop.from_config(
                params, cfg, config, model=model, lif_scan_fn=lif_scan_fn)]
        else:
            if params is not None or cfg is not None:
                raise ValueError("(params, cfg) and engines= are "
                                 "mutually exclusive")
            if isinstance(engines, Mapping):
                engines = list(engines.values())
            elif not isinstance(engines, Sequence):
                engines = [engines]
            for e in engines:
                if config.fuse_fc and not getattr(e, "fuse_fc", True):
                    raise ValueError(
                        f"EngineConfig.fuse_fc=True but engine "
                        f"'{e.modality}' was built unfused; build it "
                        f"with BatchedClosedLoop.from_config(..., config) "
                        f"or BatchedClosedLoop(..., fuse_fc=True)")
                if config.duration_us is not None:
                    if e.duration_us is None:
                        e.duration_us = config.duration_us
                    elif e.duration_us != config.duration_us:
                        raise ValueError(
                            f"engine '{e.modality}' duration "
                            f"{e.duration_us} != duration_us="
                            f"{config.duration_us}")
                if config.mesh is not None:
                    # Thread the serving mesh onto caller-provided
                    # engines; attach_mesh is idempotent for the same
                    # mesh and rejects a conflicting one.
                    attach = getattr(e, "attach_mesh", None)
                    if attach is None:
                        raise ValueError(
                            f"engine '{e.modality}' has no attach_mesh; "
                            f"a sharded StreamEngine needs every lane "
                            f"engine to support slot-axis sharding")
                    attach(config.mesh)

        self.policy = config.policy or FairQuantumPolicy(
            4 if config.fair_quantum is None else config.fair_quantum)
        self._lanes: Dict[str, EngineLane] = {}
        if not engines:
            raise ValueError("engines= must name at least one engine")
        max_streams = config.max_streams
        modalities = {e.modality for e in engines}
        if isinstance(max_streams, Mapping):
            unknown = set(max_streams) - modalities
            if unknown:
                raise ValueError(
                    f"max_streams keys {sorted(unknown)} match no engine "
                    f"modality (have {sorted(modalities)})")
        for e in engines:
            if e.modality in self._lanes:
                raise ValueError(
                    f"duplicate engine modality {e.modality!r}")
            slots = (max_streams.get(e.modality, 8)
                     if isinstance(max_streams, Mapping) else max_streams)
            if slots < 1:
                raise ValueError(f"max_streams must be >= 1, got {slots}")
            if config.mesh is not None:
                _check_slot_divisible(slots, config.mesh,
                                      f"lane '{e.modality}'")
            self._lanes[e.modality] = EngineLane(
                modality=e.modality, engine=e,
                slots=[_FREE] * slots, slot_runs=[0] * slots,
                waiting=deque(), queues={}, shape_keys=set(),
                supports_state=hasattr(e, "init_state"),
                state_streams=[_FREE] * slots)

        # Fusion-aware co-scheduling: ``_pairs`` is the bidirectional
        # stream-pairing registry (pair_streams/unpair_streams; a
        # FusionSession pairs its wings automatically); with
        # ``coschedule`` on, _dispatch fixes slot assignments up so
        # paired streams share an engine step. ``_pair_dispatch`` holds
        # the step number a paired window was dispatched at until its
        # partner's same-seq window dispatches (the paired_tick_rate
        # bookkeeping).
        self.coschedule = bool(config.coschedule)
        self._pairs: Dict[Hashable, Hashable] = {}
        self._pair_dispatch: Dict[tuple, int] = {}
        self._dispatch_no = 0
        # The fused cross-wing megastep: one jit'd dispatch serving both
        # wings' kernels, cached per (event shape key, frame shape key).
        self.megastep = bool(config.megastep)
        self._mega_exe: Dict[tuple, Callable] = {}
        if self.megastep:
            if sorted(self._lanes) != ["event", "frame"]:
                raise ValueError(
                    f"EngineConfig.megastep needs exactly one event and "
                    f"one frame lane; this engine has "
                    f"{sorted(self._lanes)}")
            for lane in self._lanes.values():
                if not hasattr(lane.engine, "_mega_parts"):
                    raise ValueError(
                        f"engine for modality {lane.modality!r} "
                        f"({type(lane.engine).__name__}) does not "
                        f"support the fused megastep")
        self._stream_lane: Dict[Hashable, str] = {}
        self._seq: Dict[Hashable, int] = {}
        self._handles: Dict[Hashable, StreamHandle] = {}
        self._auto_id = 0
        self.stream_stats: Dict[Hashable, StreamStats] = {}
        self.stats: Dict[str, int] = {"steps": 0, "windows": 0}
        # The clock finite deadlines are measured against for miss
        # telemetry (NOT for scheduling -- policies only order by
        # deadline value). Defaults to wall time; fleet drivers and
        # tests install a shared logical clock for determinism.
        self.deadline_clock: Callable[[], float] = time.perf_counter

    # -- introspection ---------------------------------------------------

    @property
    def engines(self) -> Dict[str, InferenceEngine]:
        """Engines by modality."""
        return {m: lane.engine for m, lane in self._lanes.items()}

    @property
    def loop(self) -> InferenceEngine:
        """Backwards-compatible alias: the single engine (event-only
        construction). Raises if the engine set is heterogeneous."""
        if len(self._lanes) != 1:
            raise AttributeError(
                "StreamEngine.loop is ambiguous with multiple engines; "
                "use .engines[modality]")
        return next(iter(self._lanes.values())).engine

    def modality_of(self, stream_id: Hashable) -> str:
        return self._stream_lane[stream_id]

    def compiled_shapes(self, modality: Optional[str] = None) -> set:
        """Distinct jit shape keys an engine has been stepped with."""
        if modality is None:
            if len(self._lanes) != 1:
                raise ValueError(
                    "modality required with multiple engines; have "
                    f"{sorted(self._lanes)}")
            modality = next(iter(self._lanes))
        if modality not in self._lanes:
            raise ValueError(f"no engine for modality {modality!r}; "
                             f"have {sorted(self._lanes)}")
        return set(self._lanes[modality].shape_keys)

    def warmup(self, shape_keys, modality: Optional[str] = None) -> None:
        """Precompile an engine's executables for the given shape keys.

        ``shape_keys`` is an iterable of the engine's ``shape_key``
        tuples -- for the event wing ``(batch_size, max_events,
        duration_us)``, where ``batch_size`` is normally this lane's slot
        count and ``max_events`` a power-of-two event bucket (see
        ``events.next_pow2``). Run it before the first ``submit`` so the
        first window of a new event-count bucket stops paying jit compile
        time mid-stream. ``modality`` selects the engine (optional when
        only one is configured).
        """
        if modality is None:
            if len(self._lanes) != 1:
                raise ValueError(
                    "modality required with multiple engines; have "
                    f"{sorted(self._lanes)}")
            modality = next(iter(self._lanes))
        if modality not in self._lanes:
            raise ValueError(f"no engine for modality {modality!r}; "
                             f"have {sorted(self._lanes)}")
        engine = self._lanes[modality].engine
        warm = getattr(engine, "warmup", None)
        if warm is None:
            raise ValueError(
                f"engine for modality {modality!r} "
                f"({type(engine).__name__}) does not implement warmup()")
        warm(shape_keys)

    def warmup_megastep(self, key_pairs) -> None:
        """Precompile fused megastep executables.

        ``key_pairs`` is an iterable of ``(event_shape_key,
        frame_shape_key)`` pairs -- each wing's full shape-key tuple
        (``(batch, max_events, duration_us)`` / ``(batch, height,
        width, duration_us)``). The megastep keeps its own AOT cache,
        separate from the per-engine caches, so warm it explicitly
        before serving a fused workload.
        """
        if not self.megastep:
            raise ValueError(
                "warmup_megastep on an engine without "
                "EngineConfig.megastep=True")
        ev_lane, fr_lane = self._lanes["event"], self._lanes["frame"]
        for ev_key, fr_key in key_pairs:
            self._mega_executable(ev_lane, fr_lane, tuple(ev_key),
                                  tuple(fr_key))

    def compiled_megastep_keys(self) -> set:
        """``(event_key, frame_key)`` pairs with a compiled fused
        executable (stepped or warmed)."""
        return set(self._mega_exe)

    # -- fusion pairing ---------------------------------------------------

    def pair_streams(self, a: Hashable, b: Hashable) -> None:
        """Declare two open streams (on different lanes) as the wings of
        one fusion tick: with ``coschedule`` on, the scheduler pulls
        both into the SAME engine step whenever either wins a slot, and
        the pair's same-step fraction is surfaced as
        ``paired_tick_rate`` in stream/lane telemetry.
        :class:`~repro.serving.session.FusionSession` registers its
        wings automatically; call this directly only for hand-rolled
        pairings. Idempotent for the same pair; re-pairing a stream to a
        different partner requires :meth:`unpair_streams` first."""
        for sid in (a, b):
            if sid not in self._stream_lane:
                raise KeyError(f"unknown stream {sid!r}")
        if self._stream_lane[a] == self._stream_lane[b]:
            raise ValueError(
                f"paired streams must live on different lanes; both "
                f"{a!r} and {b!r} are {self._stream_lane[a]!r}")
        if self._pairs.get(a) == b:
            return
        for sid in (a, b):
            if sid in self._pairs:
                raise ValueError(
                    f"stream {sid!r} is already paired with "
                    f"{self._pairs[sid]!r}; unpair_streams() first")
        self._pairs[a] = b
        self._pairs[b] = a

    def unpair_streams(self, stream_id: Hashable) -> None:
        """Dissolve a stream's pairing (no-op for unpaired streams);
        called automatically when either wing closes."""
        partner = self._pairs.pop(stream_id, None)
        if partner is not None:
            self._pairs.pop(partner, None)
        for key in [k for k in self._pair_dispatch
                    if k[0] == stream_id or k[0] == partner]:
            del self._pair_dispatch[key]

    # -- fleet control-plane hooks ---------------------------------------

    def _lane_named(self, modality: Optional[str]) -> EngineLane:
        """Resolve a lane by modality (optional when only one lane)."""
        if modality is None:
            if len(self._lanes) != 1:
                raise ValueError(
                    "modality required with multiple engines; have "
                    f"{sorted(self._lanes)}")
            return next(iter(self._lanes.values()))
        if modality not in self._lanes:
            raise ValueError(f"no engine for modality {modality!r}; "
                             f"have {sorted(self._lanes)}")
        return self._lanes[modality]

    def telemetry(self, modality: Optional[str] = None) -> LaneTelemetry:
        """A consistent control-plane view of one lane: aggregate queue
        depth, in-flight count, pooled sliding-horizon deadline-miss
        rate and completion rate, plus every stream's frozen
        :class:`StreamStatsSnapshot` (the rows the aggregate was
        computed from)."""
        lane = self._lane_named(modality)
        snaps = {sid: self.stream_stats[sid].snapshot()
                 for sid in lane.queues}
        in_flight = sum(
            1
            for step_recs in self._inflight
            for rec in step_recs if rec.lane is lane
            for entry in rec.entries if entry is not None)
        h_dated = sum(s.horizon_deadline_windows for s in snaps.values())
        h_missed = sum(s.horizon_missed for s in snaps.values())
        f_ticks = sum(s.fusion_ticks for s in snaps.values())
        f_paired = sum(s.fusion_ticks_paired for s in snaps.values())
        return LaneTelemetry(
            modality=lane.modality,
            slots=len(lane.slots),
            occupied=sum(1 for s in lane.slots if s is not _FREE),
            waiting=len(lane.waiting),
            queued=lane.pending(),
            in_flight=in_flight,
            windows=sum(s.windows for s in snaps.values()),
            windows_per_s=sum(s.windows_per_s for s in snaps.values()),
            deadline_miss_rate=h_missed / h_dated if h_dated else 0.0,
            streams=snaps,
            retries=lane.n_retries,
            quarantined=lane.n_quarantined,
            dead=lane.dead,
            paired_tick_rate=f_paired / f_ticks if f_ticks else 1.0)

    def dead_letters(self, modality: Optional[str] = None
                     ) -> List[DeadLetter]:
        """The lane's quarantined windows, oldest first (a copy; the
        queue itself is engine-owned)."""
        return list(self._lane_named(modality).dead_letter)

    def resize_lane(self, modality: Optional[str] = None, *,
                    slots: int, warm: bool = True) -> List[Hashable]:
        """Change one lane's batch-slot count live; returns the streams
        evicted from their slots (shrink only; they rejoin the FRONT of
        the waiting line in slot order, keeping their scheduling
        priority over never-slotted arrivals).

        Safe at any point between steps, including with pipelined
        windows in flight (collection is positional into the dispatched
        batch, so already-dispatched steps are untouched). Carried
        state survives: every live carry is parked and re-attached on
        the stream's next dispatch, so a stateful stream's windows stay
        bitwise-identical to an uninterrupted scan across the resize.
        Policy bookkeeping (e.g. ``DeadlinePolicy`` aging counters) is
        deliberately NOT touched: waiting streams keep their aging,
        evicted streams start aging from the front of the line.

        ``warm=True`` (default) amortizes the recompile: for every shape
        key the engine has already compiled at the OLD slot count, the
        corresponding new-slot-count key is precompiled through the
        engine's per-``shape_key`` AOT warmup cache, so the first step
        after the resize runs a warmed executable instead of stalling on
        a mid-serve compile. On a mesh-attached engine the new count
        must still divide over the mesh slot axis.
        """
        lane = self._lane_named(modality)
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if self.mesh is not None:
            _check_slot_divisible(slots, self.mesh,
                                  f"lane '{lane.modality}' resize")
        old = len(lane.slots)
        if slots == old:
            return []
        # Park every live carry: the state buffer is shaped by the slot
        # count, so it is rebuilt (lazily, from parked + zero rows) at
        # the next stateful dispatch. Parking copies whatever the rows
        # hold -- including pipelined async-dispatch futures.
        if lane.state is not None:
            live = [(owner, j) for j, owner in enumerate(lane.state_streams)
                    if owner is not _FREE and owner in lane.stateful]
            if live:
                self._park_rows(lane, lane.state, live)
            lane.state = None
            lane.zero_state = None
        lane.state_streams = [_FREE] * slots
        evicted: List[Hashable] = []
        if slots > old:
            lane.slots.extend([_FREE] * (slots - old))
            lane.slot_runs.extend([0] * (slots - old))
        else:
            held = [(sid, run) for sid, run in
                    zip(lane.slots, lane.slot_runs) if sid is not _FREE]
            kept, dropped = held[:slots], held[slots:]
            lane.slots = ([sid for sid, _ in kept]
                          + [_FREE] * (slots - len(kept)))
            lane.slot_runs = ([run for _, run in kept]
                              + [0] * (slots - len(kept)))
            evicted = [sid for sid, _ in dropped]
            # Front of the waiting line, slot order preserved: an
            # evicted stream was being served and must not requeue
            # behind streams that never had a slot.
            lane.waiting.extendleft(reversed(evicted))
        if warm:
            warmer = getattr(lane.engine, "warmup", None)
            compiled = getattr(lane.engine, "compiled_shape_keys", None)
            if warmer is not None:
                have = (set(compiled()) if compiled is not None
                        else set(lane.shape_keys))
                # Engine shape keys lead with the batch size (both
                # wings' contract): re-key every old-count key at the
                # new count and precompile the ones not already cached.
                want = {(slots,) + tuple(k[1:])
                        for k in have if k and k[0] == old}
                fresh = sorted(want - have)
                if fresh:
                    warmer(fresh)
        return evicted

    def drain_lane(self, modality: Optional[str] = None
                   ) -> List[StreamResult]:
        """Collect every in-flight pipelined step of ONE lane (oldest
        first), leaving other lanes' dispatched work in flight.

        This is the live-migration primitive: checkpointing a stream
        requires its lane's pending results on the host, but flushing
        the WHOLE engine would stall every other lane's pipeline. Steps
        that still hold other lanes' records stay queued (in order);
        steps left empty are dropped.

        Exception-safe: a collect failure (engine raise without
        recovery configured) leaves the in-flight deque consistent --
        already-collected records removed, everything else (this lane's
        uncollected records and every other lane's) still in flight, in
        dispatch order.
        """
        lane = self._lane_named(modality)
        out: List[StreamResult] = []
        done: Deque[List[_InflightLane]] = deque()
        try:
            while self._inflight:
                step_recs = self._inflight[0]
                # Collect this lane's records one at a time, removing
                # each from the step as it lands, so an exception
                # leaves exactly the uncollected suffix in place.
                i = 0
                while i < len(step_recs):
                    rec = step_recs[i]
                    if rec.lane is lane:
                        out.extend(self._collect_one(rec))
                        step_recs.pop(i)
                    else:
                        i += 1
                self._inflight.popleft()
                if step_recs:
                    done.append(step_recs)
        finally:
            # Steps that still hold other lanes' records go back in
            # front of whatever was not reached, preserving dispatch
            # order whether we finished or an exception unwound us.
            self._inflight.extendleft(reversed(done))
        return out

    def abort_lane(self, modality: Optional[str] = None) -> int:
        """Drop one lane's in-flight records WITHOUT collecting them
        (the lane's engine is presumed broken -- collecting would block
        on, or re-raise from, poisoned device work) and re-queue their
        windows at their sequence positions; returns the re-queued
        count. Other lanes' dispatched steps stay in flight.

        The lane's carried state is dropped wholesale -- it lived on
        the dead engine. Unsupervised stateful streams restart cold;
        supervised ones are restored from their last checkpoint by the
        :class:`~repro.fleet.supervisor.LaneSupervisor`, which is the
        caller this hook exists for (followed by
        ``replace_lane_engine``).
        """
        lane = self._lane_named(modality)
        requeue: List[tuple] = []
        remaining: Deque[List[_InflightLane]] = deque()
        while self._inflight:
            step_recs = self._inflight.popleft()
            rest = [r for r in step_recs if r.lane is not lane]
            for rec in step_recs:
                if rec.lane is not lane:
                    continue
                for i, entry in enumerate(rec.entries):
                    if entry is None:
                        continue
                    if rec.items is not None and rec.items[i] is not None:
                        requeue.append((entry[0], rec.items[i]))
            if rest:
                remaining.append(rest)
        self._inflight = remaining
        self._drop_carries(lane)
        self._requeue(lane, requeue)
        return len(requeue)

    def replace_lane_engine(self, modality: Optional[str] = None, *,
                            engine: InferenceEngine) -> None:
        """Swap one lane's engine for a rebuilt instance, clearing the
        lane's fault state (dead flag, fail streak, cooldown, retry
        counters -- the dead-letter queue is kept: it is history, not
        state). Streams, queues, slots, and policy bookkeeping survive;
        carried state does NOT (it lived on the old engine) -- restore
        stateful streams from checkpoints afterwards.

        The lane must have no windows in flight (``abort_lane`` or
        ``drain_lane`` first). The replacement must serve the same
        modality, agree on the latched ``duration_us`` (an unlatched
        replacement inherits it), support carried state if any open
        stream on the lane is stateful, and accept the engine's mesh
        when one is attached.
        """
        lane = self._lane_named(modality)
        for step_recs in self._inflight:
            for rec in step_recs:
                if rec.lane is lane and any(
                        e is not None for e in rec.entries):
                    raise ValueError(
                        f"lane {lane.modality!r} has in-flight windows; "
                        f"abort_lane() or drain_lane() before replacing "
                        f"its engine")
        if engine.modality != lane.modality:
            raise ValueError(
                f"replacement engine serves modality "
                f"{engine.modality!r}, lane is {lane.modality!r}")
        if lane.stateful and not hasattr(engine, "init_state"):
            raise ValueError(
                f"lane {lane.modality!r} has stateful streams but the "
                f"replacement engine has no carried-state support")
        if lane.engine.duration_us is not None:
            if engine.duration_us is None:
                engine.duration_us = lane.engine.duration_us
            elif engine.duration_us != lane.engine.duration_us:
                raise ValueError(
                    f"replacement duration_us={engine.duration_us} != "
                    f"lane duration_us={lane.engine.duration_us}")
        if self.mesh is not None:
            attach = getattr(engine, "attach_mesh", None)
            if attach is None:
                raise ValueError(
                    f"replacement engine for lane {lane.modality!r} has "
                    f"no attach_mesh; this engine is sharded")
            attach(self.mesh)
        if self.megastep:
            if not hasattr(engine, "_mega_parts"):
                raise ValueError(
                    f"replacement engine for lane {lane.modality!r} "
                    f"({type(engine).__name__}) does not support the "
                    f"fused megastep this engine is configured for")
            # Fused executables were lowered against the old engine's
            # abstract parameter shapes; drop them so the rebuild's
            # first fused step re-lowers against the replacement.
            self._mega_exe.clear()
        lane.engine = engine
        lane.supports_state = hasattr(engine, "init_state")
        lane.shape_keys = set()
        self._drop_carries(lane)
        lane.move_exe.clear()
        lane.dead = False
        lane.fail_streak = 0
        lane.cooldown = 0
        lane.retries.clear()
        self._log_fault("lane_replaced", lane, None, None, None)

    # -- the session-handle API ------------------------------------------

    def open(self, modality: Optional[str] = None, *,
             stream_id: Optional[Hashable] = None,
             stateful: bool = False,
             deadline: Optional[float] = None) -> StreamHandle:
        """Open a new stream and return its :class:`StreamHandle`.

        ``modality`` selects the engine lane (optional when only one is
        configured). ``stateful=True`` opts the stream into carried
        state: its engine state (the event wing: LIF membranes) chains
        across its windows, following the stream through any slot
        reassignment, until ``reset_state`` or ``close``. ``deadline``
        is the handle's default per-window deadline for deadline-aware
        policies. Modality and statefulness are latched for the
        stream's life. ``stream_id`` names the stream (auto-generated
        ``"<modality>-<n>"`` when omitted); opening an id that is
        already open raises -- close it first, or keep the old handle.
        """
        if modality is None:
            if len(self._lanes) != 1:
                raise ValueError(
                    f"modality required to open a stream with engines "
                    f"{sorted(self._lanes)}")
            lane = next(iter(self._lanes.values()))
        elif modality not in self._lanes:
            raise ValueError(f"no engine for modality {modality!r}; "
                             f"have {sorted(self._lanes)}")
        else:
            lane = self._lanes[modality]
        if stateful and not lane.supports_state:
            raise ValueError(
                f"engine for modality {lane.modality!r} "
                f"({type(lane.engine).__name__}) has no carried-state "
                f"support (no init_state); submit stateless")
        if stream_id is None:
            while True:
                stream_id = f"{lane.modality}-{self._auto_id}"
                self._auto_id += 1
                if stream_id not in self._stream_lane:
                    break
        elif stream_id in self._stream_lane:
            raise ValueError(
                f"stream {stream_id!r} is already open (bound to "
                f"modality {self._stream_lane[stream_id]!r}); close() it "
                f"before reopening the id")
        lane.queues[stream_id] = deque()
        self._stream_lane[stream_id] = lane.modality
        self._seq[stream_id] = 0
        self.stream_stats[stream_id] = StreamStats()
        if stateful:
            lane.stateful.add(stream_id)
        handle = StreamHandle(self, lane, stream_id, stateful, deadline)
        self._handles[stream_id] = handle
        return handle

    def restore(self, ckpt, *,
                stream_id: Optional[Hashable] = None) -> StreamHandle:
        """Open a stream from a :class:`~repro.serving.session.
        StreamCheckpoint` -- ``open`` + ``StreamHandle.restore`` in one
        call. The stream keeps the checkpoint's id (unless ``stream_id``
        renames it) and its default deadline."""
        handle = self.open(modality=ckpt.modality,
                           stream_id=ckpt.stream_id
                           if stream_id is None else stream_id,
                           stateful=ckpt.stateful,
                           deadline=ckpt.deadline)
        try:
            return handle.restore(ckpt)
        except Exception:
            handle.close()
            raise

    @property
    def handles(self) -> Dict[Hashable, StreamHandle]:
        """Open handles by stream id (a copy; close via the handle)."""
        return dict(self._handles)

    # -- submission (legacy id-keyed shim) -------------------------------

    def submit(self, stream_id: Hashable, window: Any, *,
               modality: Optional[str] = None,
               deadline: Optional[float] = None,
               stateful: Optional[bool] = None) -> int:
        """Queue one window on an id-keyed stream (LEGACY shim).

        The pre-session call form: the first submit of a new id opens a
        handle under the hood, later submits forward to it --
        scheduling and results are bitwise identical to driving the
        handle directly. Prefer ``open(...)`` + ``handle.submit(...)``;
        this form warns once per engine.

        ``modality`` selects the engine for a NEW stream (optional when
        only one engine is configured); known streams are bound to their
        lane. ``deadline`` is scheduling metadata consumed by
        deadline-aware policies (smaller = more urgent). ``stateful=True``
        opts a NEW stream into carried state. Like modality, statefulness
        is latched for the stream's life (default False; pass ``None``
        to leave a known stream's binding alone).
        """
        warn_deprecated_call(
            self, "id-keyed-submit",
            "StreamEngine.submit(stream_id, window, ...) is a legacy "
            "call form; use the session-handle API instead: handle = "
            "engine.open(modality=..., stateful=...); handle.submit("
            "window)")
        lane = self._resolve_lane(stream_id, modality)
        # Validation happens BEFORE any queue/seq state changes, so a
        # rejected submit neither burns a sequence number nor corrupts
        # scheduling state.
        if stateful and not lane.supports_state:
            raise ValueError(
                f"engine for modality {lane.modality!r} "
                f"({type(lane.engine).__name__}) has no carried-state "
                f"support (no init_state); submit stateless")
        handle = self._handles.get(stream_id)
        if (handle is not None and stateful is not None
                and bool(stateful) != handle.stateful):
            raise ValueError(
                f"stream {stream_id!r} is bound to stateful="
                f"{handle.stateful}; statefulness is latched "
                f"at the stream's first submit")
        if handle is None:
            # Validate BEFORE open so a rejected first submit registers
            # no stream at all (no handle, no stats entry) -- the price
            # is one redundant validate inside handle.submit (validate
            # is idempotent once the engine's duration is latched).
            lane.engine.validate(window)
            handle = self.open(modality=lane.modality,
                               stream_id=stream_id,
                               stateful=bool(stateful))
        return handle.submit(window, deadline=deadline)

    def _resolve_lane(self, stream_id: Hashable,
                      modality: Optional[str]) -> EngineLane:
        bound = self._stream_lane.get(stream_id)
        if bound is not None:
            if modality is not None and modality != bound:
                raise ValueError(
                    f"stream {stream_id!r} is bound to modality "
                    f"{bound!r}, got {modality!r}")
            return self._lanes[bound]
        if modality is None:
            if len(self._lanes) == 1:
                return next(iter(self._lanes.values()))
            raise ValueError(
                f"modality required for new stream {stream_id!r} with "
                f"engines {sorted(self._lanes)}")
        if modality not in self._lanes:
            raise ValueError(f"no engine for modality {modality!r}; "
                             f"have {sorted(self._lanes)}")
        return self._lanes[modality]

    def pending(self) -> int:
        """Windows queued across all streams and engines."""
        return sum(lane.pending() for lane in self._lanes.values())

    # -- carried state ---------------------------------------------------

    def stateful_of(self, stream_id: Hashable) -> bool:
        """Whether a known stream carries state across its windows."""
        return self._handle_of(stream_id).stateful

    def _handle_of(self, stream_id: Hashable) -> StreamHandle:
        handle = self._handles.get(stream_id)
        if handle is None:
            raise KeyError(f"unknown stream {stream_id!r}")
        return handle

    def handle(self, stream_id: Hashable) -> StreamHandle:
        """The open :class:`StreamHandle` of a known stream id (the
        lookup a fleet rebalancer uses to pick a migration victim from
        telemetry rows). Raises ``KeyError`` for unknown ids."""
        return self._handle_of(stream_id)

    def has_stream(self, stream_id: Hashable) -> bool:
        """Whether ``stream_id`` is currently open on this engine."""
        return stream_id in self._handles

    def reset_state(self, stream_id: Hashable) -> None:
        """Zero a stateful stream's carried state without retiring it;
        forwards to :meth:`StreamHandle.reset_state`."""
        self._handle_of(stream_id).reset_state()

    def retire(self, stream_id: Hashable) -> int:
        """Remove a stream entirely; forwards to
        :meth:`StreamHandle.close` (see there for semantics). Returns
        the number of queued windows discarded."""
        return self._handle_of(stream_id).close()

    def _lane_state_in(self, lane: EngineLane):
        """Phase-1 state planning for one lane's dispatch.

        Returns ``(state_in, commit)``: the slot-major state pytree to
        dispatch with (``None`` for engines without state support) and a
        ``commit(new_state)`` thunk that advances the lane's state
        tracking -- called only after EVERY lane's phase 1 succeeded, so
        a failed synchronous step leaves carried state as untouched as it
        leaves the queues. The planning is a ``state_gather`` span whose
        value is the state-move programs it issued (1 or 0); ``commit``
        only swaps references.
        """
        if not lane.supports_state or not lane.stateful:
            # No stream on this lane carries state: serve it through the
            # legacy stateless call forms. Engines start from their own
            # zero state internally (bitwise identical), the lane pays
            # nothing per step, and a split-less engine keeps the
            # pipelined deferred-"batch" fallback it would lose on the
            # stateful path.
            return None, None
        with tracing.span("state_gather", lane=lane.modality) as span:
            state_in, commit, span.value = self._gather_state(lane)
        return state_in, commit

    def _gather_state(self, lane: EngineLane):
        """:meth:`_lane_state_in` for a lane with stateful streams; also
        returns the number of state-move programs issued: 0 on the
        identity fast path (every slotted stream's carry already in its
        own row, nothing to park) and for a state with no leaves, else 1.
        """
        if lane.state is None:       # first stateful dispatch: zero state
            lane.zero_state = lane.engine.init_state(len(lane.slots))
            lane.state = lane.zero_state
        self._ensure_store(lane)
        slots = list(lane.slots)
        n = len(slots)
        pos = {owner: j for j, owner in enumerate(lane.state_streams)
               if owner is not _FREE}
        scheduled = {sid for sid in slots if sid is not _FREE}
        # Per slot, the row of concat(state, store) its carry comes from:
        # its own buffer row, its home row, or the cold-start row (free
        # slot, stateless stream, or cold-start stateful stream).
        cold = n + lane.capacity
        src = []
        for sid in slots:
            if sid is _FREE or sid not in lane.stateful:
                src.append(cold)
            elif sid in pos:
                src.append(pos[sid])
            elif sid in lane.parked:
                src.append(n + lane.parked[sid])
            else:
                src.append(cold)
        # Streams that lose their slot this step park their carry (the
        # PRE-dispatch row) in a home row no parked stream holds.
        free = _free_homes(lane)
        parks = [(owner, j, next(free))
                 for j, owner in enumerate(lane.state_streams)
                 if owner is not _FREE and owner not in scheduled]
        # Fast path: every occupied slot is a stateful stream whose carry
        # already sits in its own row, and no carry leaves the buffer.
        # Free slots' rows are dead (their results are discarded), so
        # they never force a move.
        identity = not parks and all(
            sid is _FREE or s == i for i, (sid, s) in enumerate(zip(slots,
                                                                  src)))
        leaves, treedef = jax.tree_util.tree_flatten(lane.state)
        state_in, store, programs = lane.state, lane.store, 0
        if leaves and not identity:
            idx = np.zeros((3, n), np.int32)
            idx[0] = src
            idx[2] = lane.capacity + 1
            for k, (_, j, row) in enumerate(parks):
                idx[1, k], idx[2, k] = j, row
            moved, stored = self._move_executable(lane)(
                leaves, jax.tree_util.tree_leaves(lane.store), idx)
            state_in = jax.tree_util.tree_unflatten(treedef, moved)
            store = jax.tree_util.tree_unflatten(treedef, stored)
            programs = 1

        def commit(new_state) -> None:
            lane.store = store
            for owner, _, row in parks:
                lane.parked[owner] = row
            for sid in scheduled:
                lane.parked.pop(sid, None)
            lane.state = new_state
            lane.state_streams = [
                sid if (sid is not _FREE and sid in lane.stateful)
                else _FREE
                for sid in slots]

        return state_in, commit, programs

    def _ensure_store(self, lane: EngineLane) -> None:
        """Give the lane a carry store with a home row for each of its
        stateful streams: ``capacity`` is the next power of two at or
        above their count. A store that is too small grows by doubling;
        rows keep their index, so parked carries stay where they are
        (the old cold-start row becomes a free home row)."""
        need = next_pow2(len(lane.stateful), floor=1)
        if lane.store is not None and lane.capacity >= need:
            return
        if lane.store is None:
            store = lane.engine.init_state(need + 1)
        else:
            store = jax.tree_util.tree_map(
                lambda s, z: jnp.concatenate([s, z]), lane.store,
                lane.engine.init_state(need - lane.capacity))
        lane.store = self._place_store(store)
        lane.capacity = need

    def _place_store(self, store):
        """A carry store where the state-move program expects it:
        replicated over the mesh on a mesh-attached engine (its row
        count need not divide over the slot axis), as it is otherwise."""
        if self.mesh is None:
            return store
        return jax.device_put(store, self._replicated())

    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec())

    def _park_rows(self, lane: EngineLane, state,
                   rows: List[tuple]) -> None:
        """Park carries off the hot path (restore, rollback, resize):
        for each ``(stream, row)``, copy ``state``'s row into the
        stream's home row of the store -- the one it holds, or one no
        parked stream holds -- and mark it parked. Eager: a gather and
        a scatter per leaf."""
        self._ensure_store(lane)
        free = _free_homes(lane)
        homes = [lane.parked[sid] if sid in lane.parked else next(free)
                 for sid, _ in rows]
        dst = np.asarray(homes, np.int32)
        src = np.asarray([j for _, j in rows], np.int32)
        lane.store = self._place_store(jax.tree_util.tree_map(
            lambda s, a: s.at[dst].set(jnp.asarray(a, s.dtype)[src]),
            lane.store, state))
        for (sid, _), row in zip(rows, homes):
            lane.parked[sid] = row

    def _drop_carries(self, lane: EngineLane) -> None:
        """Forget every carried state of the lane (its engine is dead
        or replaced); stateful streams restart cold unless restored."""
        lane.state = None
        lane.zero_state = None
        lane.state_streams = [_FREE] * len(lane.slots)
        lane.parked.clear()
        lane.store = None
        lane.capacity = 0

    def _move_executable(self, lane: EngineLane) -> Callable:
        """AOT-compile (once per slot count and store capacity) the
        lane's state-move program (:func:`_move_carries`). Its
        ``state_in`` keeps the engine's own state layout -- slot-sharded
        on a mesh -- so the step program takes it as it is. A miss is
        traced as a ``compile`` span of value 1."""
        key = (len(lane.slots), lane.capacity)
        exe = lane.move_exe.get(key)
        if exe is None:
            with tracing.span("compile", lane=lane.modality, value=1):
                exe = lane.move_exe[key] = self._compile_move(lane)
        return exe

    def _compile_move(self, lane: EngineLane) -> Callable:
        """The executable :meth:`_move_executable` caches."""
        state = jax.tree_util.tree_leaves(lane.zero_state)
        store = jax.tree_util.tree_leaves(lane.store)
        idx = jax.ShapeDtypeStruct((3, len(lane.slots)), jnp.int32)
        if self.mesh is None:
            spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            move = jax.jit(_move_carries)
        else:
            spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=a.sharding)
            idx = jax.ShapeDtypeStruct(idx.shape, idx.dtype,
                                       sharding=self._replicated())
            move = jax.jit(_move_carries, out_shardings=(
                [a.sharding for a in state], [a.sharding for a in store]))
        return move.lower([spec(a) for a in state],
                          [spec(a) for a in store], idx).compile()

    # -- scheduling ------------------------------------------------------

    def step(self) -> List[StreamResult]:
        """Serve one batch per engine with queued work: the head window of
        every slotted stream, one jit'd call per engine.

        Synchronous mode (``pipeline_depth == 0``, the default): returns
        this step's completed windows, and is retry-safe across the whole
        heterogeneous step -- queues are only peeked until EVERY engine's
        infer has returned, so if any engine raises (transient device
        error, OOM) no window is consumed, no stat moves, and the step can
        simply be retried.

        Pipelined mode (``pipeline_depth >= 1``): dispatches this step's
        jit'd calls without blocking on the device and returns the results
        of the step dispatched ``pipeline_depth`` steps ago (empty lists
        while the pipeline fills; ``flush()``/``run()`` drain the tail).
        The result sequence is bitwise identical to synchronous mode;
        windows are consumed at dispatch, so device failures surface at
        the later collect instead of at this call.

        Each call is traced as a ``step`` span (:mod:`repro.tracing`)
        whose step id is the call's number and whose value is the
        windows returned.
        """
        self._dispatch_no += 1
        with tracing.span("step", lane="", step=self._dispatch_no) as span:
            out = self._step()
            span.value = len(out)
        return out

    def _step(self) -> List[StreamResult]:
        """The body of :meth:`step`, inside its span."""
        if self.pipeline_depth == 0:
            ran = self._dispatch(eager=True)
            failed = self._take_failures()
            if not ran and not failed:
                return []
            out = failed + self._collect(ran)
        else:
            ran = self._dispatch(eager=False)
            if ran:
                self._inflight.append(ran)
            out = self._take_failures()
            while len(self._inflight) > self.pipeline_depth:
                out.extend(self._collect_step(self._inflight[0]))
                self._inflight.popleft()
            if not ran and self._inflight:
                # No new work: drain one in-flight step so a caller
                # looping on step() always makes progress.
                out.extend(self._collect_step(self._inflight[0]))
                self._inflight.popleft()
            if not ran and not out:
                return []
        # A no-op call (nothing dispatched, nothing collected) does not
        # count as a step; a failed one raises before reaching here.
        self.stats["steps"] += 1
        return out

    def _dispatch(self, *, eager: bool) -> List[_InflightLane]:
        """Assign slots and launch every lane's jit'd call.

        Phase 1 assigns every servable lane's slots (then, with fusion
        pairs registered, runs the co-scheduling fixup so paired wings
        share this step). Phase 2 peeks the queue heads and, per lane,
        either runs infer to completion (``eager``, the synchronous
        retry-safe mode: an exception from ANY lane leaves every queue
        untouched), dispatches asynchronously (pipelined, engine has the
        async split), or just prepares the batch (pipelined fallback) --
        with ``megastep``, both wings instead go through ONE fused jit'd
        call. Phase 3 commits the pops, slot run counts, and
        carried-state tracking only after every lane's dispatch
        succeeded.
        """
        with tracing.span("assign"):
            work = self._assign()
        ran: List[_InflightLane] = []
        state_commits: List[tuple] = []
        if self.megastep and len(work) == 2:
            # Both wings have work this step: one fused jit'd dispatch
            # serves the whole step (megastep requires exactly the
            # event+frame lanes, so len(work)==2 identifies them). A
            # single-winged step falls through to the per-lane path
            # below -- that is the degraded case, and it keeps the
            # ordinary dispatch semantics.
            try:
                recs, commits = self._mega_dispatch(work, eager)
            except Exception:
                if self.recovery is None:
                    raise
                # The fused call serves both wings, so a fault in
                # either aborts it with every queue and carry untouched
                # (state planning commits only on success). Fall back
                # to per-lane dispatch for this very step: the failure
                # localizes to the wing that actually faulted and
                # ordinary recovery (retry/cooldown/quarantine) applies
                # to it alone, exactly as without the megastep.
                recs = None
            if recs is not None:
                ran.extend(recs)
                state_commits.extend(commits)
                work = []
        for lane, heads in work:
            try:
                rec, commit = self._dispatch_lane(lane, heads, eager)
            except Exception as exc:
                if self.recovery is None:
                    raise
                # Queues are untouched (heads were only peeked):
                # charge a retry to every window in the attempted
                # batch, put the lane on cooldown, and keep serving
                # other lanes.
                self._note_lane_failure(lane, heads, exc)
                continue
            ran.append(rec)
            if commit is not None:
                state_commits.append(commit)
        # Commit: every lane dispatched -- pop the served heads and
        # advance each lane's carried state. The parks ran inside the
        # gather's program, so this span issues no device operation.
        for lane, commit, new_state in state_commits:
            with tracing.span("state_park", lane=lane.modality):
                commit(new_state)
        for rec in ran:
            lane = rec.lane
            rec.items = [None] * len(rec.entries)
            for i, slot in enumerate(rec.entries):
                if slot is None:
                    continue
                sid = lane.slots[slot]
                entry = lane.queues[sid].popleft()
                lane.slot_runs[slot] += 1
                self.stream_stats[sid].queued -= 1
                rec.entries[i] = (sid, entry.seq, entry.deadline)
                rec.items[i] = entry
                if self._pairs:
                    self._note_pair_dispatch(sid, entry.seq)
        return ran

    def _assign(self) -> List[tuple]:
        """Phase 1 of :meth:`_dispatch`: the policy assigns every
        servable lane's slots (fail-fast and backoff first, with
        recovery), co-scheduling fixes up fusion pairs; returns
        ``(lane, heads)`` for each lane with a queued head in a slot."""
        active: List[EngineLane] = []
        for lane in self._lanes.values():
            if self.recovery is not None:
                if lane.dead:
                    # Fail-fast: a dead lane never calls its engine;
                    # queued windows are dead-lettered immediately so
                    # paired fusion ticks keep completing (degraded)
                    # until replace_lane_engine installs a rebuild.
                    self._fail_fast_lane(lane)
                    continue
                if lane.cooldown > 0:
                    # Deterministic backoff: sit out whole engine steps
                    # (not wall time) after a failed lane step.
                    lane.cooldown -= 1
                    continue
            self.policy.assign(lane)
            active.append(lane)
        if self._pairs and self.coschedule:
            self._coschedule(active)
        work: List[tuple] = []
        for lane in active:
            heads = [
                lane.queues[sid][0].item if sid is not _FREE else None
                for sid in lane.slots
            ]
            if any(w is not None for w in heads):
                work.append((lane, heads))
        return work

    def _dispatch_lane(self, lane: EngineLane, heads: List,
                       eager: bool) -> tuple:
        """One lane's dispatch (phase 2 of :meth:`_dispatch`): returns
        ``(record, state_commit_or_None)``; raises with the lane's
        queues untouched."""
        batch = _pack(lane, heads)
        key = lane.engine.shape_key(batch)
        state_in, state_commit = self._lane_state_in(lane)
        dispatch = getattr(lane.engine, "infer_dispatch", None)
        collect = getattr(lane.engine, "infer_collect", None)
        has_split = dispatch is not None and collect is not None
        new_state = None
        with tracing.span("launch", lane=lane.modality):
            if eager or (state_in is not None and not has_split):
                # Synchronous infer. A stateful engine WITHOUT the async
                # split also lands here under pipelining: its carry must
                # advance in dispatch order, so its infer cannot wait
                # for the (later) collect.
                if state_in is None:
                    # Stateless lanes ride the engines' legacy call form
                    # by design; the deprecation nudge is for end users.
                    with suppress_api_deprecations():
                        results = lane.engine.infer(batch)
                    kind, pending = "results", results
                else:
                    results, new_state = lane.engine.infer(batch, state_in)
                    kind, pending = "results", results
            elif has_split:
                if state_in is None:
                    kind, pending = "handle", dispatch(batch)
                else:
                    # Async dispatch: new_state is a pytree of device
                    # futures, threaded into the NEXT dispatch without
                    # ever blocking on (or copying to) the host.
                    pending, new_state = dispatch(batch, state_in)
                    kind = "handle"
            else:
                kind, pending = "batch", batch
        rec = _InflightLane(
            lane=lane, key=key,
            entries=[None if w is None else slot
                     for slot, w in enumerate(heads)],
            kind=kind, pending=pending,
            prev_carry=self._prev_carry(lane, heads, state_in),
            step=self._dispatch_no)
        commit = ((lane, state_commit, new_state)
                  if state_commit is not None else None)
        return rec, commit

    def _prev_carry(self, lane: EngineLane, heads: List, state_in):
        """The rollback target quarantine restores: where each
        dispatched stateful stream's pre-window carry sits, as ``(state
        fed in, row)`` (recovery only; nothing is copied unless a
        rollback happens)."""
        if self.recovery is None or state_in is None:
            return None
        return {sid: (state_in, slot)
                for slot, sid in enumerate(lane.slots)
                if (sid is not _FREE and sid in lane.stateful
                    and heads[slot] is not None)}

    # -- fusion co-scheduling and the fused megastep ---------------------

    def _coschedule(self, lanes: List[EngineLane]) -> None:
        """Fusion-aware fixup after policy assignment: for every paired
        stream holding a slot with queued work, pull its partner into
        the partner's lane for this SAME step -- into a free slot when
        one exists, else by evicting a seated stream that is not itself
        half of a co-scheduled pair (the evictee rejoins the FRONT of
        its waiting line, keeping its priority over never-seated
        arrivals). Dead, cooling, or drained partner lanes are left
        alone: a surviving wing is never blocked on a wing that cannot
        run. Scheduling-only -- per-window results are bitwise
        unchanged; only WHICH step serves a window moves."""
        by_mod = {lane.modality: lane for lane in lanes}
        for lane in lanes:
            for sid in lane.slots:
                if sid is _FREE or not lane.queues.get(sid):
                    continue
                partner = self._pairs.get(sid)
                if partner is None:
                    continue
                plane = by_mod.get(self._stream_lane.get(partner))
                if (plane is None or partner in plane.slots
                        or not plane.queues.get(partner)):
                    continue
                self._seat_partner(plane, partner)

    def _seat_partner(self, lane: EngineLane, sid: Hashable) -> bool:
        """Seat ``sid`` in ``lane`` for this step (co-scheduling only);
        returns whether a slot was won."""
        free = next((i for i, cur in enumerate(lane.slots)
                     if cur is _FREE), None)
        if free is None:
            # Evict: the first victim whose own pairing does not tie it
            # to this step (unpaired, or its partner is not seated).
            for i, cur in enumerate(lane.slots):
                p = self._pairs.get(cur)
                if p is None:
                    free = i
                    break
                plane = self._lanes.get(self._stream_lane.get(p, ""))
                if plane is None or p not in plane.slots:
                    free = i
                    break
            if free is None:
                return False
            evicted = lane.slots[free]
            lane.slot_runs[free] = 0
            if lane.queues.get(evicted):
                # Front of the line: the evictee was seated and must
                # not requeue behind streams that never had a slot
                # (the resize_lane eviction rule).
                lane.waiting.appendleft(evicted)
        lane.slots[free] = sid
        lane.slot_runs[free] = 0
        try:
            lane.waiting.remove(sid)
        except ValueError:
            pass
        # Mirror the policies' take-side bookkeeping: a seated stream's
        # aging restarts exactly as if the policy had taken it.
        forget = getattr(self.policy, "forget", None)
        if forget is not None:
            forget(sid)
        return True

    def _note_pair_dispatch(self, sid: Hashable, seq: int) -> None:
        """Pair bookkeeping at dispatch commit: when both wings of a
        paired tick have dispatched, credit a fusion tick to both
        streams' stats (paired when the wings shared one engine step).
        """
        partner = self._pairs.get(sid)
        if partner is None:
            return
        other_step = self._pair_dispatch.pop((partner, seq), None)
        if other_step is None:
            self._pair_dispatch[(sid, seq)] = self._dispatch_no
            return
        paired = int(other_step == self._dispatch_no)
        for s in (sid, partner):
            st = self.stream_stats.get(s)
            if st is not None:
                st.fusion_ticks += 1
                st.fusion_ticks_paired += paired

    def _mega_executable(self, ev_lane: EngineLane, fr_lane: EngineLane,
                         ev_key, fr_key) -> Callable:
        """AOT-compile (once) the fused two-wing executable for a pair
        of per-wing shape keys. The program is the wings' OWN run
        functions lowered side by side -- XLA schedules the SNN scan and
        the ternary conv stack in one compiled call -- so each wing's
        half stays bitwise-identical to that wing's separate executable.
        """
        cache_key = (ev_key, fr_key)
        exe = self._mega_exe.get(cache_key)
        if exe is None:
            with tracing.span("compile", value=1):
                ev_run, ev_abs = ev_lane.engine._mega_parts(ev_key)
                fr_run, fr_abs = fr_lane.engine._mega_parts(fr_key)

                def mega(ev_args, fr_args):
                    return ev_run(*ev_args), fr_run(*fr_args)

                exe = jax.jit(mega).lower(ev_abs, fr_abs).compile()
                self._mega_exe[cache_key] = exe
        return exe

    def _mega_dispatch(self, work: List[tuple], eager: bool) -> tuple:
        """Both wings' dispatch through one fused jit'd call; returns
        ``(records, state_commits)`` shaped exactly as two ordinary
        per-lane dispatches, so collection, recovery, quarantine, and
        pipelining downstream are unchanged. Raises with every queue
        untouched (the caller charges the failure to both lanes)."""
        by_mod = {lane.modality: (lane, heads) for lane, heads in work}
        ev_lane, ev_heads = by_mod["event"]
        fr_lane, fr_heads = by_mod["frame"]
        ev_batch = _pack(ev_lane, ev_heads)
        ev_key = ev_lane.engine.shape_key(ev_batch)
        fr_batch = _pack(fr_lane, fr_heads)
        fr_key = fr_lane.engine.shape_key(fr_batch)
        ev_state, ev_commit = self._lane_state_in(ev_lane)
        fr_state, fr_commit = self._lane_state_in(fr_lane)
        with tracing.span("launch"):
            exe = self._mega_executable(ev_lane, fr_lane, ev_key, fr_key)
            ev_out, fr_out = exe(
                ev_lane.engine._mega_args(ev_batch, ev_state),
                fr_lane.engine._mega_args(fr_batch, fr_state))
            ev_pending, ev_new = ev_lane.engine._mega_split(
                ev_out, ev_batch, ev_state)
            fr_pending, fr_new = fr_lane.engine._mega_split(
                fr_out, fr_batch, fr_state)
            if eager:
                # Synchronous mode stays retry-safe: materialize BOTH
                # wings' results before any queue state moves.
                ev_kind, ev_pending = (
                    "results", ev_lane.engine.infer_collect(ev_pending))
                fr_kind, fr_pending = (
                    "results", fr_lane.engine.infer_collect(fr_pending))
            else:
                ev_kind = fr_kind = "handle"
        recs: List[_InflightLane] = []
        commits: List[tuple] = []
        for lane, heads, key, kind, pending, state_in, commit, new in (
                (ev_lane, ev_heads, ev_key, ev_kind, ev_pending,
                 ev_state, ev_commit, ev_new),
                (fr_lane, fr_heads, fr_key, fr_kind, fr_pending,
                 fr_state, fr_commit, fr_new)):
            recs.append(_InflightLane(
                lane=lane, key=key,
                entries=[None if w is None else slot
                         for slot, w in enumerate(heads)],
                kind=kind, pending=pending,
                prev_carry=self._prev_carry(lane, heads, state_in),
                step=self._dispatch_no))
            if commit is not None:
                commits.append((lane, commit, new))
        # Records in lane declaration order, exactly as the per-lane
        # path emits them, so result ordering is bitwise unchanged.
        order = {m: i for i, m in enumerate(self._lanes)}
        recs.sort(key=lambda r: order[r.lane.modality])
        return recs, commits

    def _collect(self, ran: List[_InflightLane]) -> List[StreamResult]:
        """Block on a dispatched step's device results and emit them."""
        out: List[StreamResult] = []
        for rec in ran:
            out.extend(self._collect_one(rec))
        return out

    def _collect_step(self, step_recs: List[_InflightLane]
                      ) -> List[StreamResult]:
        """Collect one in-flight step's records, removing each from the
        (still-enqueued) step list as it lands -- so an exception from
        an engine without recovery configured leaves exactly the
        uncollected suffix in flight instead of desynchronizing the
        shared deque (pop-or-restore)."""
        out: List[StreamResult] = []
        while step_recs:
            out.extend(self._collect_one(step_recs[0]))
            step_recs.pop(0)
        return out

    def _collect_one(self, rec: _InflightLane) -> List[StreamResult]:
        """Collect one lane's record of one dispatched step, traced as a
        ``collect`` span under the step id of its dispatch; the
        per-stream stats loop is an ``account`` span of value 0 (the
        engine's own ``account`` span counts the windows)."""
        with tracing.span("collect", lane=rec.lane.modality, step=rec.step):
            return self._collect_record(rec)

    def _collect_record(self, rec: _InflightLane) -> List[StreamResult]:
        """The body of :meth:`_collect_one`."""
        lane = rec.lane
        try:
            if rec.kind == "results":
                results = rec.pending
            elif rec.kind == "handle":
                results = lane.engine.infer_collect(rec.pending)
            else:
                with suppress_api_deprecations():
                    results = lane.engine.infer(rec.pending)
        except Exception as exc:
            if self.recovery is None:
                raise
            return self._recover_record(rec, exc)
        lane.shape_keys.add(rec.key)
        lane.fail_streak = 0
        with tracing.span("account"):
            out: List[StreamResult] = []
            wall_t = time.perf_counter()
            rcfg = self.recovery
            for slot, entry in enumerate(rec.entries):
                if entry is None:
                    continue
                sid, seq, deadline = entry
                res = results[slot]
                if (rcfg is not None and rcfg.quarantine_nonfinite
                        and res.logits is not None
                        and not np.all(np.isfinite(np.asarray(res.logits)))):
                    # Poison: NaNs are deterministic, a retry would just
                    # recompute them -- quarantine immediately, roll the
                    # carry back, keep the stream alive.
                    out.append(self._quarantine_entry(
                        rec, slot, "non-finite logits"))
                    continue
                lane.retries.pop((sid, seq), None)
                st = self.stream_stats[sid]
                st.windows += 1
                st.energy_mj += res.energy_mj
                st.latency_ms_sum += res.latency_ms
                st.realtime_windows += int(res.realtime)
                # Deadline-miss telemetry: a finite deadline is an
                # instant on the engine's deadline_clock; collecting the
                # window after that instant is a miss. Feeds the sliding
                # per-stream horizon the fleet control plane reads.
                missed = (None if deadline is None
                          else self.deadline_clock() > deadline)
                st.note_completion(wall_t, st.queued, missed)
                out.append(StreamResult(
                    stream_id=sid, seq=seq, result=res,
                    modality=lane.modality))
                self.stats["windows"] += 1
        return out

    # -- fault recovery --------------------------------------------------

    def _log_fault(self, kind: str, lane: EngineLane,
                   sid: Optional[Hashable], seq: Optional[int],
                   error: Optional[str]) -> None:
        self.fault_log.append({
            "step": int(self.stats["steps"]), "kind": kind,
            "modality": lane.modality, "stream": sid, "seq": seq,
            "error": error})

    def _take_failures(self) -> List[StreamResult]:
        out, self._pending_failures = self._pending_failures, []
        return out

    def _rollback_carry(self, rec: _InflightLane,
                        sid: Hashable) -> None:
        """Restore a stream's carry to its pre-window value (captured
        at this record's dispatch) and orphan any state rows it owns."""
        lane = rec.lane
        if rec.prev_carry is None or sid not in rec.prev_carry:
            return
        state_in, slot = rec.prev_carry[sid]
        self._park_rows(lane, state_in, [(sid, slot)])
        for j, owner in enumerate(lane.state_streams):
            if owner is not _FREE and owner == sid:
                lane.state_streams[j] = _FREE

    def _scrub_stream_inflight(self, lane: EngineLane, sid: Hashable,
                               skip: Optional[_InflightLane] = None
                               ) -> List[tuple]:
        """Remove a stream's windows from the lane's still-in-flight
        records (their device results chained on a rolled-back carry
        and must not be served); returns ``(sid, _Queued)`` rows to
        re-queue."""
        requeue: List[tuple] = []
        for step_recs in self._inflight:
            for r in step_recs:
                if r is skip or r.lane is not lane:
                    continue
                for i, entry in enumerate(r.entries):
                    if entry is not None and entry[0] == sid:
                        r.entries[i] = None
                        if r.items is not None and r.items[i] is not None:
                            requeue.append((sid, r.items[i]))
                            r.items[i] = None
        return requeue

    def _requeue(self, lane: EngineLane, entries: List[tuple]) -> None:
        """Put failed windows back on their streams' queues at their
        sequence positions (stable merge by seq -- re-queued windows
        precede later submissions, and re-queues from successive failed
        records interleave correctly)."""
        by_sid: Dict[Hashable, List[_Queued]] = {}
        for sid, q in entries:
            by_sid.setdefault(sid, []).append(q)
        for sid, qs in by_sid.items():
            if sid not in lane.queues:
                continue             # stream closed while in flight
            lane.queues[sid] = deque(sorted(
                list(lane.queues[sid]) + qs, key=lambda e: e.seq))
            self.stream_stats[sid].queued += len(qs)
            if sid not in lane.slots and sid not in lane.waiting:
                lane.waiting.append(sid)
            for q in qs:
                self._log_fault("requeue", lane, sid, q.seq, None)

    def _quarantine_entry(self, rec: _InflightLane, slot: int,
                          error: str) -> StreamResult:
        """Dead-letter one window of a collected record: emit its
        failed result, roll back the stream's carry, and pull the
        stream's still-in-flight successors (they chained on the
        poisoned carry) back onto the queue."""
        lane = rec.lane
        sid, seq, deadline = rec.entries[slot]
        item = None
        if rec.items is not None and rec.items[slot] is not None:
            item = rec.items[slot].item
        lane.retries.pop((sid, seq), None)
        lane.dead_letter.append(DeadLetter(
            stream_id=sid, seq=seq, modality=lane.modality, item=item,
            deadline=deadline, error=error))
        lane.n_quarantined += 1
        self.stream_stats[sid].quarantined += 1
        self._log_fault("quarantine", lane, sid, seq, error)
        if sid in lane.stateful:
            self._rollback_carry(rec, sid)
            self._requeue(lane,
                          self._scrub_stream_inflight(lane, sid, skip=rec))
        return StreamResult(
            stream_id=sid, seq=seq, result=None, modality=lane.modality,
            status="failed", error=error)

    def _recover_record(self, rec: _InflightLane,
                        exc: Exception) -> List[StreamResult]:
        """A record failed at collect (pipelined): retry its windows --
        re-queued at their seq positions with carries rolled back -- or
        quarantine the ones that exhausted ``max_retries``; put the
        lane on backoff and maybe declare it dead."""
        lane = rec.lane
        rcfg = self.recovery
        err = f"{type(exc).__name__}: {exc}"
        out: List[StreamResult] = []
        requeue: List[tuple] = []
        for slot, entry in enumerate(rec.entries):
            if entry is None:
                continue
            sid, seq, _deadline = entry
            count = lane.retries.get((sid, seq), 0) + 1
            if count > rcfg.max_retries:
                out.append(self._quarantine_entry(rec, slot, err))
                continue
            lane.retries[(sid, seq)] = count
            lane.n_retries += 1
            self.stream_stats[sid].retries += 1
            self._log_fault("retry", lane, sid, seq, err)
            if sid in lane.stateful:
                self._rollback_carry(rec, sid)
                requeue.extend(
                    self._scrub_stream_inflight(lane, sid, skip=rec))
            if rec.items is not None and rec.items[slot] is not None:
                requeue.append((sid, rec.items[slot]))
        self._requeue(lane, requeue)
        lane.fail_streak += 1
        lane.cooldown = max(lane.cooldown, rcfg.backoff_steps)
        if lane.fail_streak >= rcfg.dead_after and not lane.dead:
            lane.dead = True
            self._log_fault("lane_dead", lane, None, None, err)
        return out

    def _note_lane_failure(self, lane: EngineLane, heads: List,
                           exc: Exception) -> None:
        """A lane's synchronous dispatch failed with its queues still
        untouched (two-phase dispatch only peeks until every lane's
        infer returns): charge a retry to each window in the attempted
        batch, quarantine the ones over budget, back the lane off."""
        rcfg = self.recovery
        err = f"{type(exc).__name__}: {exc}"
        for slot, sid in enumerate(lane.slots):
            if sid is _FREE or heads[slot] is None:
                continue
            entry = lane.queues[sid][0]
            count = lane.retries.get((sid, entry.seq), 0) + 1
            if count > rcfg.max_retries:
                lane.queues[sid].popleft()
                self.stream_stats[sid].queued -= 1
                lane.retries.pop((sid, entry.seq), None)
                lane.dead_letter.append(DeadLetter(
                    stream_id=sid, seq=entry.seq, modality=lane.modality,
                    item=entry.item, deadline=entry.deadline, error=err))
                lane.n_quarantined += 1
                self.stream_stats[sid].quarantined += 1
                self._log_fault("quarantine", lane, sid, entry.seq, err)
                self._pending_failures.append(StreamResult(
                    stream_id=sid, seq=entry.seq, result=None,
                    modality=lane.modality, status="failed", error=err))
                continue
            lane.retries[(sid, entry.seq)] = count
            lane.n_retries += 1
            self.stream_stats[sid].retries += 1
            self._log_fault("retry", lane, sid, entry.seq, err)
        lane.fail_streak += 1
        lane.cooldown = max(lane.cooldown, rcfg.backoff_steps)
        if lane.fail_streak >= rcfg.dead_after and not lane.dead:
            lane.dead = True
            self._log_fault("lane_dead", lane, None, None, err)

    def _fail_fast_lane(self, lane: EngineLane) -> None:
        """Dead-lane mode: dead-letter everything queued without
        touching the engine, emitting failed results immediately so
        closed-loop callers (and fusion pairing) keep ticking."""
        for sid in list(lane.queues):
            q = lane.queues[sid]
            while q:
                entry = q.popleft()
                self.stream_stats[sid].queued -= 1
                lane.dead_letter.append(DeadLetter(
                    stream_id=sid, seq=entry.seq, modality=lane.modality,
                    item=entry.item, deadline=entry.deadline,
                    error="lane dead"))
                lane.n_quarantined += 1
                self.stream_stats[sid].quarantined += 1
                self._log_fault("quarantine", lane, sid, entry.seq,
                                "lane dead")
                self._pending_failures.append(StreamResult(
                    stream_id=sid, seq=entry.seq, result=None,
                    modality=lane.modality, status="failed",
                    error="lane dead"))

    def flush(self) -> List[StreamResult]:
        """Collect every in-flight pipelined step (oldest first)."""
        out: List[StreamResult] = []
        while self._inflight:
            out.extend(self._collect_step(self._inflight[0]))
            self._inflight.popleft()
        return out

    @property
    def in_flight(self) -> int:
        """Dispatched-but-uncollected pipeline steps."""
        return len(self._inflight)

    def run(self) -> List[StreamResult]:
        """Drain every queue (and the pipeline); results in completion
        order -- identical, order and values, for any ``pipeline_depth``."""
        out: List[StreamResult] = []
        while self.pending() or self._inflight:
            out.extend(self.step())
        return out

    @property
    def mean_occupancy(self) -> float:
        """Average served windows per step (batching efficiency; with
        multiple engines this sums over the per-engine batches)."""
        return (self.stats["windows"] / self.stats["steps"]
                if self.stats["steps"] else 0.0)
