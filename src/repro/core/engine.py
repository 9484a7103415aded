"""The unified heterogeneous engine API: one protocol, two accelerators.

ColibriES is a heterogeneous platform: event streams feed the SNE (spiking
CNN) and frames feed CUTIE (ternary CNN), through one shared FC + cluster
front end. This module defines the small :class:`InferenceEngine` protocol
that lets the serving layer treat both wings uniformly:

  * ``modality``      -- which input kind the engine consumes
                         ("event" / "frame"), declared as a class attr;
  * ``duration_us``   -- the engine's latched control-tick length (the
                         one-bin-width-per-engine contract);
  * ``validate(item)``        -- reject a bad submission *before* any
                                 queue state changes;
  * ``prepare(items, batch_size)`` -- pad per-slot items into the engine's
                                 fixed batch buffer;
  * ``init_state(batch_size)`` -- the engine's zero carried-state pytree,
                                 slot-major (leading axis = batch slot).
                                 Stateless engines return an EMPTY pytree
                                 (``{}``) so the contract stays uniform;
  * ``infer(batch)``          -- one jit'd call, one result per slot.
                                 With carried state:
                                 ``infer(batch, state) -> (results,
                                 new_state)`` -- ``new_state`` is a
                                 device pytree, feedable straight back
                                 into the next call so per-stream state
                                 (e.g. the SNN's LIF membranes) chains
                                 windows into one uninterrupted scan;
  * ``shape_key(batch)``      -- the jit compilation key of a prepared
                                 batch (engines with data-dependent
                                 padding, like the event engine's
                                 power-of-two event buckets, expose how
                                 many distinct executables a workload
                                 compiles).

Optional extensions (duck-typed -- the serving layer probes with
``getattr`` so third-party engines implementing only the base protocol,
or even only its stateless pre-state subset, still plug in unchanged --
an engine without ``init_state`` is simply served stateless):

  * ``infer_dispatch(batch[, state])`` / ``infer_collect(pending)`` --
    the async split of ``infer``: dispatch launches the jit'd call and
    returns an opaque pending handle WITHOUT blocking on the device
    (with ``state``: ``(pending, new_state)``, where ``new_state`` is
    made of jax async-dispatch futures -- the pipelined serving path
    threads it into the NEXT dispatch so carried state stays
    device-resident between steps, never round-tripping the host);
    collect blocks and turns the handle into per-slot results. The
    pipelined ``StreamEngine.step`` uses these to overlap host-side
    packing of step k+1 with device compute of step k; engines without
    them are served synchronously.
  * ``warmup(shape_keys)``    -- precompile executables for a set of
    shape keys so no window pays compile time mid-stream.
  * ``export_state(state, slot)`` / ``import_state(state, slot,
    payload)`` -- the checkpoint/restore pair: export turns one slot's
    row of a slot-major carried-state pytree into a HOST-serializable
    (numpy) payload; import splices such a payload back into a row of a
    (possibly different process's) slot-major state. Together they make
    a stream's carry migratable between engine processes without the
    serving layer knowing the state's structure --
    ``StreamHandle.checkpoint()`` / ``restore()`` are built on exactly
    this pair, with a generic leading-axis-slicing fallback for engines
    that do not implement it.

Concrete engines:

  * :class:`~repro.core.pipeline.BatchedClosedLoop` -- the event->SNN wing
    (defined in ``core/pipeline.py``, conforms to this protocol);
  * :class:`FrameTCNEngine` (here) -- the frame->ternary-CNN wing: frame
    normalization (``core/frames.py``), the CUTIE TCN (``core/tcn.py``,
    2-bit packed weights through the ``ternary_matmul`` Pallas kernel),
    and per-stream CUTIE latency/energy accounting
    (:meth:`~repro.core.energy.KrakenModel.frame_loop`).

Both engines return :class:`~repro.core.pipeline.ClosedLoopResult` rows,
so per-stream stats, PWM actuation, and energy breakdowns are uniform
across modalities.
"""
from __future__ import annotations

from typing import (Any, Callable, Dict, Hashable, List, Optional, Protocol,
                    Sequence, Tuple, runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import frames as fr
from repro.core._api import (EngineConfig, suppress_api_deprecations,
                             warn_deprecated_call)
from repro.core.energy import KrakenModel
from repro.core.pipeline import (ClosedLoopResult, _check_slot_divisible,
                                 _mesh_slot_info, _replicate_to_mesh,
                                 export_state_slot, import_state_slot,
                                 pwm_from_logits)
from repro.core.tcn import TCNConfig, pack_tcn, tcn_apply, tcn_layer_macs

__all__ = ["InferenceEngine", "FrameTCNEngine"]


@runtime_checkable
class InferenceEngine(Protocol):
    """What the serving layer needs from an accelerator wing."""

    modality: str
    duration_us: Optional[int]

    def validate(self, item: Any) -> None:
        """Raise ValueError if ``item`` cannot be served by this engine.
        Must not mutate queue-visible state on failure (latching the
        engine's ``duration_us`` on first success is allowed)."""
        ...

    def prepare(self, items: Sequence[Optional[Any]], *,
                batch_size: int) -> Any:
        """Pad one item per slot (None = empty slot) into a batch."""
        ...

    def init_state(self, batch_size: int) -> Any:
        """Zero carried-state pytree, slot-major; empty if stateless."""
        ...

    def infer(self, batch: Any, state: Any = None):
        """Run one jit'd call; one result per slot, None for empty slots.

        Without ``state``: returns the result list (stateless legacy
        call). With ``state``: returns ``(results, new_state)``."""
        ...

    def shape_key(self, batch: Any) -> Hashable:
        """The jit compilation key of a prepared batch."""
        ...


class FrameTCNEngine:
    """The CUTIE wing: frame batch -> ternary CNN -> actuation.

    One jit'd call normalizes and classifies a whole
    :class:`~repro.core.frames.PaddedFrameBatch`; the Kraken model then
    accounts each slot with its own pixel count and operand activity.
    Frames are dense, so the jit shape is fixed by ``(batch_size, H, W)``
    alone -- one executable per slot count, no data-dependent bucketing.
    """

    modality = "frame"

    def __init__(
        self,
        params,
        cfg: TCNConfig,
        *,
        model: Optional[KrakenModel] = None,
        duration_us: Optional[int] = None,
        window_ms: float = 300.0,
        prepacked: bool = False,
        mesh=None,
    ):
        self.cfg = cfg
        self.packed = params if prepacked else pack_tcn(params)
        self.model = model or KrakenModel()
        self.duration_us = duration_us
        self.window_ms = window_ms
        self.layer_macs = tcn_layer_macs(cfg)
        self.total_macs = float(sum(self.layer_macs))
        self.mesh = None
        # Explicit executable cache: shape_key -> AOT-compiled callable.
        self._exe: Dict[Tuple[int, ...], Callable] = {}
        if mesh is not None:
            self.attach_mesh(mesh)

    @classmethod
    def from_config(cls, params, cfg: TCNConfig, config: EngineConfig, *,
                    model: Optional[KrakenModel] = None,
                    prepacked: bool = False):
        """Construct from the unified :class:`EngineConfig` surface.
        ``fuse_fc`` and the serving-layer fields do not apply to the
        frame wing and are ignored."""
        return cls(params, cfg, model=model, prepacked=prepacked,
                   duration_us=config.duration_us,
                   window_ms=config.window_ms, mesh=config.mesh)

    def attach_mesh(self, mesh) -> None:
        """Shard the slot axis over ``mesh``; same contract as
        :meth:`BatchedClosedLoop.attach_mesh` (idempotent for the same
        mesh, errors on a different one or after compilation). The
        packed ternary weights are pinned replicated."""
        if mesh is None or mesh == self.mesh:
            return
        if self.mesh is not None:
            raise ValueError(
                "engine is already attached to a different mesh; one "
                "engine serves one mesh for its whole lifetime")
        if self._exe:
            raise RuntimeError(
                "attach_mesh after executables were compiled: attach the "
                "mesh at construction (EngineConfig(mesh=...)) or before "
                "the first infer/warmup call")
        self.mesh = mesh
        self.packed = _replicate_to_mesh(self.packed, mesh)

    # -- protocol --------------------------------------------------------

    def validate(self, frame: fr.FrameWindow) -> None:
        if frame.shape != (self.cfg.height, self.cfg.width):
            raise ValueError(
                f"frame shape {frame.shape} != engine geometry "
                f"({self.cfg.height}, {self.cfg.width})")
        if self.duration_us is None:
            self.duration_us = frame.duration_us
        elif frame.duration_us != self.duration_us:
            raise ValueError(
                f"frame period {frame.duration_us} != engine period "
                f"{self.duration_us} (one tick length per engine)")

    def prepare(self, items: Sequence[Optional[fr.FrameWindow]], *,
                batch_size: int) -> fr.PaddedFrameBatch:
        return fr.pad_frame_windows(
            items, batch_size=batch_size, duration_us=self.duration_us,
            height=self.cfg.height, width=self.cfg.width)

    def shape_key(self, batch: fr.PaddedFrameBatch) -> Hashable:
        return (batch.batch_size, *batch.frame_shape, batch.duration_us)

    def init_state(self, batch_size: int) -> Dict:
        """The CUTIE wing is feedforward per frame: no carried state.

        Returns the empty pytree so the engine still satisfies the
        uniform state contract -- stateful serving threads ``{}`` through
        unchanged, and a ``stateful=True`` frame stream is simply a
        no-op carry."""
        return {}

    def _build_run(self) -> Callable:
        """Normalize + classify + readout for one frame batch (unjitted).
        Factored out of :meth:`_executable` so the serving layer's fused
        cross-wing megastep can lower the SAME function next to the
        event wing's -- one compiled program, bitwise-identical outputs.
        """
        cfg = self.cfg

        def run(packed, pixels):
            with jax.named_scope("cutie"):
                out = tcn_apply(packed, fr.normalize_frames(pixels), cfg)
            with jax.named_scope("readout"):
                logits = out["logits"]
                return (jnp.argmax(logits, -1), pwm_from_logits(logits),
                        logits, out["activity_per_stream"])

        return run

    def _executable(self, key: Tuple[int, ...]) -> Callable:
        """AOT-compile (once) and return the executable for a shape key,
        ``(batch_size, height, width, duration_us)`` -- compilation is
        eager so :meth:`warmup` can pull it off the serving path. Each
        miss is traced as a ``compile`` span of value 1."""
        exe = self._exe.get(key)
        if exe is None:
            with tracing.span("compile", lane=self.modality, value=1):
                exe = self._exe[key] = self._compile(key)
        return exe

    def _compile(self, key: Tuple[int, ...]) -> Callable:
        """The executable :meth:`_executable` caches for ``key``."""
        b, h, w = int(key[0]), int(key[1]), int(key[2])
        run = self._build_run()

        px_sh = pk_sh = None
        if self.mesh is not None:
            # Dense frames shard the same way as the event wing:
            # pixels split on the slot axis, packed weights
            # replicated, each device classifying its own rows
            # (tcn_apply is row-independent, so shards are bitwise
            # equal to the full batch).
            from jax.sharding import NamedSharding, PartitionSpec as P
            _check_slot_divisible(b, self.mesh, "sharded-engine")
            ax, _ = _mesh_slot_info(self.mesh)
            run = jax.shard_map(
                run, mesh=self.mesh,
                in_specs=(P(), P(ax, None, None, None)),
                out_specs=(P(ax), P(ax, None), P(ax, None),
                           {k: P(ax) for k in
                            ("conv1", "conv2", "fc1", "fc2")}),
                check_vma=False)
            px_sh = NamedSharding(self.mesh, P(ax, None, None, None))
            pk_sh = NamedSharding(self.mesh, P())
        px_abs = jax.ShapeDtypeStruct((b, h, w, 1), jnp.float32,
                                      sharding=px_sh)
        pk_abs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.asarray(a).dtype,
                                           sharding=pk_sh),
            self.packed)
        return jax.jit(run).lower(pk_abs, px_abs).compile()

    def warmup(self, shape_keys) -> None:
        """Precompile executables for ``(batch_size, height, width[,
        duration_us])`` shape keys (duration is not part of the compiled
        shape for dense frames; it is accepted for symmetry with
        ``shape_key``). A 3-tuple key borrows the engine's latched
        ``duration_us`` and therefore requires one -- warming an
        unlatched engine with 3-tuples would silently cache executables
        under a ``(b, h, w, None)`` key that no served batch ever hits.
        """
        for key in shape_keys:
            key = tuple(key)
            if len(key) == 3:
                if self.duration_us is None:
                    raise ValueError(
                        "3-tuple shape key needs a pinned tick period: "
                        "latch duration_us first (pass duration_us= at "
                        "construction or validate a frame) or pass the "
                        "full (batch, height, width, duration_us) key")
                key = (*key, self.duration_us)
            if len(key) != 4:
                raise ValueError(
                    f"shape key must be (batch, height, width[, "
                    f"duration_us]), got {key}")
            if (key[1], key[2]) != (self.cfg.height, self.cfg.width):
                raise ValueError(
                    f"shape key geometry {key[1:3]} != engine geometry "
                    f"({self.cfg.height}, {self.cfg.width})")
            self._executable(key)

    def compiled_shape_keys(self) -> set:
        """Shape keys with a compiled executable (stepped or warmed)."""
        return set(self._exe)

    # -- cross-wing megastep adapters ------------------------------------
    # Counterparts of BatchedClosedLoop's: the serving layer's fused
    # megastep lowers this wing's run next to the event wing's in one
    # jit'd program (see EngineConfig.megastep).

    def _mega_parts(self, key):
        """``(run_fn, abstract_args)`` for a shape key, for fused
        cross-wing compilation (single-device only)."""
        if self.mesh is not None:
            raise ValueError(
                "the fused megastep does not compose with a mesh-attached "
                "engine")
        b, h, w = int(key[0]), int(key[1]), int(key[2])
        px_abs = jax.ShapeDtypeStruct((b, h, w, 1), jnp.float32)
        pk_abs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.asarray(a).dtype),
            self.packed)
        return self._build_run(), (pk_abs, px_abs)

    def _mega_args(self, batch: fr.PaddedFrameBatch, state):
        """Concrete argument tuple matching :meth:`_mega_parts` (the
        CUTIE wing carries no state; ``state`` is ignored)."""
        return (self.packed, batch.pixels)

    def _mega_split(self, out, batch: fr.PaddedFrameBatch, state):
        """Split megastep outputs into the ``(pending, state)`` pair
        :meth:`infer_dispatch` returns (no-op carry passthrough)."""
        preds, pwm, logits, activity = out
        return (batch, preds, pwm, logits, activity), state

    def infer_dispatch(self, batch: fr.PaddedFrameBatch, state=None):
        """Launch the jit'd call without host sync; see
        :meth:`BatchedClosedLoop.infer_dispatch`. With ``state`` (the
        empty pytree) returns ``(pending, state)`` -- the uniform
        stateful dispatch shape, carrying nothing."""
        exe = self._executable(self.shape_key(batch))
        pixels = jnp.asarray(batch.pixels)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            ax, _ = _mesh_slot_info(self.mesh)
            pixels = jax.device_put(
                pixels, NamedSharding(self.mesh, P(ax, None, None, None)))
        preds, pwm, logits, activity = exe(self.packed, pixels)
        pending = (batch, preds, pwm, logits, activity)
        return pending if state is None else (pending, state)

    def infer_collect(self, pending) -> List[Optional[ClosedLoopResult]]:
        """Fetch a dispatched batch's outputs (a ``fetch`` span) and
        account each slot (an ``account`` span whose value is the
        windows accounted)."""
        batch, preds, pwm, logits, activity = pending
        with tracing.span("fetch", lane=self.modality):
            preds = np.asarray(preds)
            pwm = np.asarray(pwm)
            logits = np.asarray(logits)
            activity = {k: np.asarray(v) for k, v in activity.items()}

        results: List[Optional[ClosedLoopResult]] = []
        with tracing.span("account", lane=self.modality,
                          value=int(batch.occupied.sum())):
            for b in range(batch.batch_size):
                if not batch.occupied[b]:
                    results.append(None)
                    continue
                # CUTIE runs its full dense schedule regardless of
                # content; per-stream differences surface as switching
                # activity.
                act = float(np.mean([v[b] for v in activity.values()]))
                acct = self.model.frame_loop(
                    float(batch.num_pixels[b]), self.total_macs,
                    activity=act)
                latency = float(acct["total_time_ms"])
                proc_ms = (acct["stages"]["preprocessing"]["time_ms"]
                           + acct["stages"]["tcn_inference"]["time_ms"])
                period_ms = max(self.window_ms, proc_ms)
                results.append(ClosedLoopResult(
                    label_pred=preds[b:b + 1],
                    pwm=pwm[b:b + 1],
                    latency_ms=latency,
                    energy_mj=float(acct["total_energy_mj"]),
                    breakdown=acct,
                    realtime=latency <= self.window_ms,
                    sustained_rate_hz=1000.0 / period_ms,
                    logits=logits[b:b + 1],
                ))
        return results

    def export_state(self, state, slot: int):
        """Checkpoint one slot's carry -- trivially the empty pytree for
        the feedforward CUTIE wing, through the same engine-agnostic
        contract as the event wing."""
        return export_state_slot(state, slot)

    def import_state(self, state, slot: int, payload):
        """Restore one slot's carry (a no-op splice of the empty
        pytree)."""
        return import_state_slot(state, slot, payload)

    def infer(self, batch: fr.PaddedFrameBatch, state=None):
        """Synchronous convenience: dispatch + collect back to back.
        With ``state``: returns ``(results, state)`` (no-op carry).
        The stateless direct form is deprecated -- thread the (empty)
        state or serve through ``StreamEngine.open(...)``."""
        if state is None:
            warn_deprecated_call(
                self, "stateless-infer",
                "stateless FrameTCNEngine.infer(batch) is a legacy call "
                "form; pass carried state -- infer(batch, "
                "init_state(batch_size)) -- or serve frames through the "
                "session API: StreamEngine.open(...).submit(window)")
            return self.infer_collect(self.infer_dispatch(batch))
        pending, new_state = self.infer_dispatch(batch, state)
        return self.infer_collect(pending), new_state

    def infer_frames(self, frames: Sequence[Optional[fr.FrameWindow]], *,
                     batch_size: Optional[int] = None,
                     ) -> List[Optional[ClosedLoopResult]]:
        """Convenience: pad a frame list and run it as one batch."""
        frames = list(frames)
        if not frames and not batch_size:
            return []
        for f in frames:
            if f is not None:
                self.validate(f)
        # Compat wrapper: drives the stateless form deliberately.
        with suppress_api_deprecations():
            return self.infer(self.prepare(
                frames, batch_size=batch_size or len(frames)))
