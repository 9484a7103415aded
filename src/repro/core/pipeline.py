"""The ColibriES closed control loop: acquire -> preprocess -> infer -> act.

Mirrors the paper's Sec. III decomposition ("data acquisition on the FC
through the dedicated DVS interface, data processing on the engines, which
includes a spike preprocessing step in the cluster and a spike train
inference step in the SNE, and actuators control using PWM signals").

The functional computation (voxelization + SCNN inference + control-signal
generation) runs in JAX; latency/energy are produced by the calibrated
:class:`~repro.core.energy.KrakenModel`. The pipeline also reports the
sustained closed-loop rate under double-buffered acquisition (the DVS
interface + uDMA run autonomously, so window N+1 is acquired while window N
is processed -- the paper's real-time claim: 164.5 ms processing fits in the
300 ms window period).

Two entry points share one batched substrate (and the batched engine is
the event wing of the :class:`~repro.core.engine.InferenceEngine`
protocol -- its frame-wing sibling is
:class:`~repro.core.engine.FrameTCNEngine`):

  * :class:`BatchedClosedLoop` -- the engine core: a padded
    :class:`~repro.core.events.PaddedEventBatch` of ``B`` event windows is
    voxelized and inferred in ONE jit'd call (batched segment-sum
    voxelization + batch folded through the SNN / LIF kernels), then each
    stream gets its own Kraken latency/energy accounting from per-stream
    firing rates and true (unpadded) event counts.
  * :class:`ClosedLoopPipeline` -- the paper's single-window loop, now a
    thin B=1 wrapper over the batched path; existing callers and the
    energy model are untouched.

Every per-stream op in the batched path (convs, pools, T*B-row matmuls,
per-row reductions, elementwise LIF dynamics, exact-integer voxel sums) is
row-independent, so results for a stream are bitwise identical whether it
runs alone or inside a batch -- asserted by the parity tests.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import events as ev
from repro.core._api import (EngineConfig, suppress_api_deprecations,
                             warn_deprecated_call)
from repro.core.energy import KrakenModel, NOMINAL
from repro.core.tiling import SNE_NEURON_CAPACITY, plan_network

__all__ = ["ClosedLoopResult", "BatchedClosedLoop", "ClosedLoopPipeline",
           "pwm_from_logits", "export_state_slot", "import_state_slot"]


def export_state_slot(state, slot: int):
    """One slot's row of a slot-major carried-state pytree, as a
    host-serializable (numpy) pytree.

    The generic implementation behind the engines' duck-typed
    ``export_state``: every leaf is sliced at ``slot`` along its leading
    (batch) axis and copied to the host. An engine whose state is not a
    plain leading-axis pytree overrides ``export_state`` instead.
    """
    return jax.tree_util.tree_map(lambda a: np.asarray(a[slot]), state)


def import_state_slot(state, slot: int, payload):
    """A new slot-major state equal to ``state`` with row ``slot``
    replaced by ``payload`` (an :func:`export_state_slot`-shaped host
    pytree). Bitwise inverse of export for f32 leaves: export -> import
    round-trips the carry exactly, which is what makes checkpoints
    migration-safe."""
    return jax.tree_util.tree_map(
        lambda a, p: a.at[slot].set(jnp.asarray(p, a.dtype)), state, payload)


# ----------------------------------------------------------------------
# Slot-axis sharding plumbing (shared by both engine wings).
#
# A mesh-attached engine runs ONE jit'd step over the whole device mesh
# with the batch-slot axis partitioned along the mesh's data axis. The
# mechanism is shard_map -- each device traces the same per-shard
# computation over its (B/n, ...) rows -- NOT GSPMD auto-partitioning:
# under GSPMD the voxelize scatter-add and the (T, B) -> (T*B) row
# merges inside the SNN would compile to all-reduce / all-gather pairs.
# shard_map makes collective-freedom structural (nothing in the step
# mentions another shard), and because every per-stream op in the step
# is row-independent (the PR 1 batch-size-invariance contract), each
# shard's rows are bitwise identical to the same rows of a full-batch
# single-device call.
# ----------------------------------------------------------------------

def _mesh_slot_info(mesh):
    """(axis name, axis size) the engines shard slots over."""
    from repro.distributed.mesh import slot_axis
    ax = slot_axis(mesh)
    return ax, dict(zip(mesh.axis_names, mesh.devices.shape))[ax]


def _replicate_to_mesh(tree, mesh):
    """Pin a pytree fully replicated on every mesh device (params)."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(tree, NamedSharding(mesh, PartitionSpec()))


def _slot_shard_to_mesh(tree, mesh):
    """Pin a slot-major pytree with its leading axis over the slot axis."""
    from repro.distributed.sharding import slot_shardings
    return jax.device_put(tree, slot_shardings(mesh, tree))


def _check_slot_divisible(batch_size: int, mesh, what: str) -> None:
    ax, n = _mesh_slot_info(mesh)
    if batch_size % n != 0:
        raise ValueError(
            f"{what} batch size {batch_size} does not divide over the "
            f"mesh slot axis '{ax}' ({n} devices); size lanes/batches in "
            f"multiples of the mesh size (EngineConfig.max_streams)")


def _shard_wrap(run: Callable, mesh, state_tree) -> Callable:
    """shard_map ``run`` over the slot axis: batch arrays and the
    slot-major state split on their leading dim, params replicated,
    every output slot-major. ``check_vma=False``: replicated params are
    closed over per shard; nothing in the step crosses shards."""
    from repro.distributed.sharding import slot_state_pspecs
    from jax.sharding import PartitionSpec as P
    ax, _ = _mesh_slot_info(mesh)
    row = P(ax, None)
    state_specs = slot_state_pspecs(state_tree, mesh)
    in_specs = (P(), row, row, row, row, row, state_specs)
    out_specs = (P(ax), row, row,
                 {k: P(ax) for k in state_tree}, state_specs)
    return jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pwm_from_logits(logits: jnp.ndarray, num_channels: int = 4) -> jnp.ndarray:
    """Map classifier logits to PWM duty cycles in [0, 1].

    A stand-in controller: a fixed linear map from class posteriors to
    ``num_channels`` actuation channels (e.g. quadrotor motor setpoints).
    The paper's PWM update itself is <1 us and negligible.
    """
    probs = jax.nn.softmax(logits, axis=-1)
    n_cls = probs.shape[-1]
    # Deterministic mixing matrix (no trainable state in the actuation stub).
    mix = (np.arange(n_cls)[:, None] * np.arange(1, num_channels + 1)[None, :])
    mix = np.cos(mix / n_cls * np.pi).astype(np.float32)
    # Broadcast-multiply-sum instead of ``probs @ mix``: a (1, n_cls) GEMV
    # and a (B, n_cls) GEMM accumulate in different orders on CPU; this
    # per-row reduction is batch-size invariant (bitwise B=1 == batched).
    duty = (probs[..., :, None] * jnp.asarray(mix)).sum(axis=-2)
    return jnp.clip(0.5 + 0.5 * duty, 0.0, 1.0)


def _check_scan_fn(fn: Optional[Callable]) -> None:
    """Reject two-argument legacy ``lif_scan_fn`` callables up front.

    The engine threads carried state (``v0``) through its scan hook, so
    a pre-stateful-streaming ``lambda c, p: ...`` would only fail with
    an opaque TypeError deep inside the first jit trace. Catch it at
    construction with a message that names the fix. Callables whose
    signature cannot be inspected are let through (they fail loudly at
    trace time if genuinely incompatible).
    """
    if fn is None:
        return
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return
    n_pos = 0
    for p in sig.parameters.values():
        if p.kind == p.VAR_POSITIONAL:
            return
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            n_pos += 1
    if n_pos < 3:
        raise ValueError(
            f"lif_scan_fn must accept (currents, lif_params, v0): the "
            f"engine threads carried state through the scan (stateful "
            f"streaming). Pass repro.kernels.ops.lif_scan itself -- it "
            f"already takes v0 -- instead of a two-argument wrapper "
            f"(got signature {sig})")


@dataclasses.dataclass
class ClosedLoopResult:
    label_pred: np.ndarray
    pwm: np.ndarray
    latency_ms: float
    energy_mj: float
    breakdown: Dict[str, Any]
    realtime: bool
    sustained_rate_hz: float
    # Pre-actuation classifier logits, (1, num_classes). Both wings emit
    # them so a FusionSession can combine modalities BEFORE actuation
    # (late logit fusion); None for engines that predate the field.
    logits: Optional[np.ndarray] = None


class BatchedClosedLoop:
    """Batched event-window -> actuation engine with per-stream accounting.

    One jit'd call voxelizes and infers a whole :class:`PaddedEventBatch`;
    the Kraken latency/energy model then runs per stream on that stream's
    true event count and firing rates. Empty batch slots (zero valid
    events) flow through the same computation and yield ``None`` results.

    Executables are cached explicitly per ``shape_key`` --
    ``(batch_size, max_events, duration_us)`` -- via the jax AOT API:
    :meth:`warmup` precompiles a set of keys up front so the first window
    of a new event-count bucket never pays compile time mid-stream, and
    :meth:`compiled_shape_keys` exposes what the cache holds. Callers that
    keep shapes fixed (the streaming engine's slot buffers, or the B=1
    wrapper's power-of-two event buckets) compile once per bucket.

    This is the event wing of the :class:`~repro.core.engine.
    InferenceEngine` protocol: ``validate``/``prepare``/``infer``/
    ``shape_key`` below are what the engine-agnostic
    :class:`~repro.serving.stream.StreamEngine` drives (plus the optional
    ``infer_dispatch``/``infer_collect`` split it uses to pipeline device
    compute against host packing). ``duration_us`` is the
    one-bin-width-per-engine contract: all windows served by one engine
    share a bin width (pass it at construction to pin it, or leave
    ``None`` to latch it from the first validated window).

    The network is the config object's: ``cfg`` (an
    :class:`~repro.core.snn.SNNConfig` for the Table II SCNN, a
    :class:`~repro.core.spikformer.SpikformerConfig` for Spikformer)
    supplies the sensor geometry and ``time_bins`` the voxelizer reads,
    ``init_state(B)``, ``apply(params, vox, ...)`` -- returning logits,
    per-stream firing rates and the final state -- and ``lif_layers()``,
    the LIF layer table the tiling plans and the per-stream accounting
    read.

    ``fuse_fc=True`` routes the fully-connected layers (the SCNN's
    fc1/fc2, Spikformer's token linears) through the fused synapse+LIF
    Pallas kernel (``kernels/fc_lif_scan.py``): their synaptic-current
    tensors never round-trip HBM, with bitwise-identical results to the
    unfused path.

    Carried state (stateful streaming): the network is stateful across
    the control loop, and this engine exposes that as a first-class
    slot-major pytree -- one (B, ...) membrane plane per LIF layer.
    ``init_state(B)`` makes the zero (cold-start) state; ``infer(batch,
    state)`` returns ``(results, new_state)``, and feeding ``new_state``
    back chains the windows bitwise-exactly into one uninterrupted scan
    (the ``s0 = v0 >= v_th`` contract from ``core/lif.py``). The state
    stays a device pytree end to end: ``infer_dispatch(batch, state)``
    returns the new state as jax async-dispatch futures, so a pipelined
    caller threads membranes from step to step without any host
    round-trip. Calls without ``state`` run the same executable from the
    zero state and drop the final state -- the legacy stateless
    behaviour, bitwise unchanged.
    """

    modality = "event"

    def __init__(
        self,
        params,
        cfg,
        *,
        model: Optional[KrakenModel] = None,
        lif_scan_fn: Optional[Callable] = None,
        window_ms: float = 300.0,
        duration_us: Optional[int] = None,
        fuse_fc: bool = False,
        mesh=None,
    ):
        self.params = params
        self.cfg = cfg
        self.mesh = None
        self.model = model or KrakenModel()
        self.window_ms = window_ms
        self.duration_us = duration_us
        self.fuse_fc = fuse_fc
        # SNE executes the LIF layers; tile plans sized by each layer's
        # output volume against SNE's neuron capacity.
        self.layers = cfg.lif_layers()
        self.plans = plan_network([(l.name, l.shape) for l in self.layers],
                                  SNE_NEURON_CAPACITY)
        self.fanouts = tuple(l.fanout for l in self.layers)
        self._volumes = {l.name: float(np.prod(l.shape)) for l in self.layers}
        _check_scan_fn(lif_scan_fn)
        self._lif_scan_fn = lif_scan_fn
        # Explicit executable cache: shape_key -> AOT-compiled callable.
        self._exe: Dict[Any, Callable] = {}
        # Zero-state cache: stateless dispatches reuse one zero pytree per
        # batch size instead of re-allocating it every step.
        self._zero_state: Dict[int, Any] = {}
        if mesh is not None:
            self.attach_mesh(mesh)

    @classmethod
    def from_config(cls, params, cfg, config: EngineConfig, *,
                    model: Optional[KrakenModel] = None,
                    lif_scan_fn: Optional[Callable] = None):
        """Construct from the unified :class:`EngineConfig` surface (the
        serving-irrelevant fields -- ``max_streams``, ``policy``,
        ``fair_quantum``, ``pipeline_depth`` -- belong to the
        ``StreamEngine`` layer and are ignored here)."""
        return cls(params, cfg, model=model, lif_scan_fn=lif_scan_fn,
                   window_ms=config.window_ms,
                   duration_us=config.duration_us,
                   fuse_fc=config.fuse_fc, mesh=config.mesh)

    # -- Slot-axis sharding ----------------------------------------------

    def attach_mesh(self, mesh) -> None:
        """Shard this engine's slot axis over ``mesh``'s data axis.

        Params are pinned replicated on every mesh device; from here on
        every executable compiles as one shard_map'd step over the mesh
        and every batch/state input is resharded slot-major on dispatch.
        Must happen before any executable is compiled (single-device
        executables bind unsharded layouts), and a second attach with a
        *different* mesh is an error -- re-attaching the same mesh is a
        no-op, which is what lets ``StreamEngine`` thread one mesh to
        caller-provided engines idempotently.
        """
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
        self.params = _replicate_to_mesh(self.params, mesh)
        self._zero_state.clear()    # rebuild slot-sharded on next use

    # -- InferenceEngine protocol ----------------------------------------

    def init_state(self, batch_size: int):
        """The zero carried-state pytree for ``batch_size`` slots.

        Slot-major: one (batch_size, ...) f32 membrane plane per LIF
        layer (the network config's ``init_state``). Zero membrane
        is the cold-start condition, so a window inferred from
        ``init_state`` is bitwise identical to a stateless call.

        On a mesh-attached engine the state comes back slot-sharded when
        ``batch_size`` divides over the slot axis; indivisible sizes
        (e.g. the B=1 scratch state the checkpoint-restore splice uses)
        stay plain host-side arrays -- they are only ever sliced and
        spliced, never inferred.
        """
        state = self.cfg.init_state(batch_size)
        if self.mesh is not None:
            _, n = _mesh_slot_info(self.mesh)
            if batch_size % n == 0:
                state = _slot_shard_to_mesh(state, self.mesh)
        return state

    def _zero_state_for(self, batch_size: int):
        st = self._zero_state.get(batch_size)
        if st is None:
            st = self._zero_state[batch_size] = self.init_state(batch_size)
        return st

    def validate(self, window: ev.EventWindow) -> None:
        """Submission-time check: latch/enforce the engine bin width."""
        if self.duration_us is None:
            self.duration_us = window.duration_us
        elif window.duration_us != self.duration_us:
            raise ValueError(
                f"window duration {window.duration_us} != engine duration "
                f"{self.duration_us} (one bin width per engine)")

    def prepare(self, items: Sequence[Optional[ev.EventWindow]], *,
                batch_size: int) -> ev.PaddedEventBatch:
        """Pad one window per slot into the engine's fixed batch buffer.

        Event counts are padded to power-of-two buckets, so jit caches at
        most log2 distinct executables over the engine's lifetime and the
        buffer shrinks back after a burst window.
        """
        bucket = ev.next_pow2(max(
            (w.num_events for w in items if w is not None), default=1))
        return ev.pad_event_windows(
            items, max_events=bucket, batch_size=batch_size,
            duration_us=self.duration_us)

    def shape_key(self, batch: ev.PaddedEventBatch):
        return (batch.batch_size, batch.max_events, batch.duration_us)

    def _build_run(self, duration_us: int) -> Callable:
        """Voxelize + infer + readout for one window duration (unjitted).

        One executable serves both the stateless and the stateful path:
        it always takes the slot-major state pytree and always returns
        the per-layer final membranes (stateless callers feed the cached
        zero state and drop the output).
        """
        cfg, scan, fuse = self.cfg, self._lif_scan_fn, self.fuse_fc

        def run(params, x, y, t, p, valid, state):
            with jax.named_scope("voxelize"):
                vox = ev.voxelize_batch(
                    x, y, t, p, valid, duration_us=duration_us,
                    time_bins=cfg.time_bins, height=cfg.height,
                    width=cfg.width,
                )
            logits, rates, new_state = cfg.apply(
                params, vox, lif_scan_fn=scan, fuse_fc=fuse, state=state)
            with jax.named_scope("readout"):
                return (jnp.argmax(logits, -1), pwm_from_logits(logits),
                        logits, rates, new_state)

        return run

    def _executable(self, key) -> Callable:
        """AOT-compile (once) and return the executable for a shape key.

        ``key`` is ``(batch_size, max_events, duration_us)``. Compilation
        happens eagerly here -- not lazily inside jit on first call -- so
        :meth:`warmup` can pull the cost off the serving critical path.
        Each miss is traced as a ``compile`` span of value 1.
        """
        exe = self._exe.get(key)
        if exe is None:
            with tracing.span("compile", lane=self.modality, value=1):
                exe = self._exe[key] = self._compile(key)
        return exe

    def _compile(self, key) -> Callable:
        """The executable :meth:`_executable` caches for ``key``."""
        b, n_ev, duration_us = key
        run = self._build_run(int(duration_us))
        shard = None
        if self.mesh is not None:
            from repro.distributed.sharding import slot_shardings
            from jax.sharding import NamedSharding, PartitionSpec as P
            _check_slot_divisible(b, self.mesh, "sharded-engine")
            run = _shard_wrap(run, self.mesh, self._zero_state_for(b))
            shard = dict(
                params=NamedSharding(self.mesh, P()),
                row=NamedSharding(
                    self.mesh,
                    P(_mesh_slot_info(self.mesh)[0], None)),
                state=slot_shardings(self.mesh,
                                     self._zero_state_for(b)))
        row_sh = shard["row"] if shard else None
        ev_i32 = jax.ShapeDtypeStruct((b, n_ev), jnp.int32,
                                      sharding=row_sh)
        ev_bool = jax.ShapeDtypeStruct((b, n_ev), jnp.bool_,
                                       sharding=row_sh)

        def abstract(tree, sh_tree=None):
            one = lambda a, s=None: jax.ShapeDtypeStruct(
                jnp.shape(a), jnp.asarray(a).dtype, sharding=s)
            if sh_tree is None:
                return jax.tree_util.tree_map(one, tree)
            return jax.tree_util.tree_map(one, tree, sh_tree)

        params_abs = abstract(
            self.params,
            jax.tree_util.tree_map(lambda _: shard["params"],
                                   self.params) if shard else None)
        state_abs = abstract(self._zero_state_for(b),
                             shard["state"] if shard else None)
        return jax.jit(run).lower(
            params_abs, ev_i32, ev_i32, ev_i32, ev_i32,
            ev_bool, state_abs).compile()

    def warmup(self, shape_keys) -> None:
        """Precompile executables for the given shape keys.

        Each key is ``(batch_size, max_events, duration_us)``; a 2-tuple
        ``(batch_size, max_events)`` uses the engine's latched
        ``duration_us``. Call before serving so no window pays compile
        time mid-stream (``StreamEngine.warmup`` forwards here).
        """
        for key in shape_keys:
            key = tuple(key)
            if len(key) == 2:
                if self.duration_us is None:
                    raise ValueError(
                        "2-tuple shape key needs a latched duration_us; "
                        "pass (batch, max_events, duration_us) or pin "
                        "duration_us at construction")
                key = (*key, self.duration_us)
            if len(key) != 3:
                raise ValueError(
                    f"shape key must be (batch_size, max_events[, "
                    f"duration_us]), got {key}")
            self._executable(key)

    def compiled_shape_keys(self) -> set:
        """Shape keys with a compiled executable (stepped or warmed)."""
        return set(self._exe)

    # -- cross-wing megastep adapters ------------------------------------
    # The serving layer's fused megastep (EngineConfig.megastep) lowers
    # this wing's run function NEXT TO the frame wing's into one jit'd
    # program, so XLA schedules the fc_lif_scan SNN scan and the ternary
    # conv stack together and the engine pays one dispatch per step.
    # The run and abstract signature are exactly what `_executable`
    # lowers on its own, which is what keeps the fused call
    # bitwise-identical to this wing's separate executable.

    def _mega_parts(self, key):
        """``(run_fn, abstract_args)`` for a shape key, for fused
        cross-wing compilation. Single-device only (the serving layer
        rejects megastep + mesh before ever calling this)."""
        if self.mesh is not None:
            raise ValueError(
                "the fused megastep does not compose with a mesh-attached "
                "engine")
        b, n_ev, duration_us = key
        run = self._build_run(int(duration_us))
        ev_i32 = jax.ShapeDtypeStruct((b, n_ev), jnp.int32)
        ev_bool = jax.ShapeDtypeStruct((b, n_ev), jnp.bool_)
        abstract = lambda tree: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.asarray(a).dtype), tree)
        return run, (abstract(self.params), ev_i32, ev_i32, ev_i32,
                     ev_i32, ev_bool, abstract(self._zero_state_for(b)))

    def _mega_args(self, batch: ev.PaddedEventBatch, state):
        """Concrete argument tuple matching :meth:`_mega_parts`'s
        abstract signature (``state=None`` = the cached zero state,
        exactly as the stateless dispatch path)."""
        if state is None:
            state = self._zero_state_for(batch.batch_size)
        return (self.params, batch.x, batch.y, batch.t, batch.p,
                batch.valid, state)

    def _mega_split(self, out, batch: ev.PaddedEventBatch, state):
        """Split this wing's megastep outputs into the same
        ``(pending, new_state)`` pair :meth:`infer_dispatch` returns, so
        :meth:`infer_collect` (and every recovery path built on it)
        serves fused steps unchanged."""
        preds, pwm, logits, rates_ps, new_state = out
        return (batch, preds, pwm, logits, rates_ps), new_state

    def _account(self, num_events: int,
                 rates: Dict[str, float]) -> Dict[str, Any]:
        """Kraken latency/energy for one stream's window (pure float math).

        The spikes into each LIF layer are the events (first layer) or
        its source layer's spikes: firing rate x neurons x time bins."""
        t = self.cfg.time_bins
        layer_in_spikes = tuple(
            float(num_events) if l.source is None
            else rates[l.source] * self._volumes[l.source] * t
            for l in self.layers)
        acct = self.model.closed_loop(
            events=float(num_events),
            layer_in_spikes=layer_in_spikes,
            layer_fanout=self.fanouts,
            layer_passes=[p.passes for p in self.plans],
        )
        # Per-layer mean firing rates for this window: observable per
        # stream (e.g. to watch carried membrane shift the dynamics).
        acct["firing_rates"] = dict(rates)
        return acct

    def infer_dispatch(self, batch: ev.PaddedEventBatch, state=None):
        """Launch the jit'd call for a padded batch WITHOUT host sync.

        Returns an opaque pending handle for :meth:`infer_collect` -- or,
        when ``state`` is given (a slot-major pytree from
        :meth:`init_state` or a previous dispatch), the pair
        ``(pending, new_state)``. The device arrays inside are jax
        futures (async dispatch): the caller can keep packing the next
        batch on the host while the device computes this one -- the
        overlap the pipelined ``StreamEngine.step`` exploits -- and
        ``new_state`` is itself made of futures, so chaining it into the
        next dispatch keeps membranes device-resident with no host sync.
        """
        stateless = state is None
        if stateless:
            state = self._zero_state_for(batch.batch_size)
        exe = self._executable(self.shape_key(batch))
        arrs = (jnp.asarray(batch.x), jnp.asarray(batch.y),
                jnp.asarray(batch.t), jnp.asarray(batch.p),
                jnp.asarray(batch.valid))
        if self.mesh is not None:
            # Reshard inputs to what the executable was lowered for. For
            # state chained from the previous dispatch this is a no-op
            # (already slot-sharded); host-rebuilt states (slot
            # reassignment, checkpoint splices) get scattered here --
            # the ONLY cross-device movement on the serving path.
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.distributed.sharding import slot_shardings
            row = NamedSharding(
                self.mesh, P(_mesh_slot_info(self.mesh)[0], None))
            arrs = jax.device_put(arrs, (row,) * 5)
            state = jax.device_put(state,
                                   slot_shardings(self.mesh, state))
        preds, pwm, logits, rates_ps, new_state = exe(
            self.params, *arrs, state)
        pending = (batch, preds, pwm, logits, rates_ps)
        return pending if stateless else (pending, new_state)

    def infer_collect(self, pending) -> List[Optional[ClosedLoopResult]]:
        """Fetch a dispatched batch's outputs and account each stream.

        This is the only point that blocks on the device (the implicit
        ``np.asarray`` device-to-host copies, traced as a ``fetch``
        span); the per-slot accounting is an ``account`` span whose value
        is the windows accounted.
        """
        batch, preds, pwm, logits, rates_ps = pending
        with tracing.span("fetch", lane=self.modality):
            preds = np.asarray(preds)
            pwm = np.asarray(pwm)
            logits = np.asarray(logits)
            rates_ps = {k: np.asarray(v) for k, v in rates_ps.items()}

        results: List[Optional[ClosedLoopResult]] = []
        with tracing.span("account", lane=self.modality,
                          value=int(batch.occupied.sum())):
            for b in range(batch.batch_size):
                if not batch.occupied[b]:
                    results.append(None)
                    continue
                # A real-but-quiet window (zero events) is still occupied
                # and gets a result; only window=None slots yield None.
                n_ev = int(batch.num_events[b])
                acct = self._account(
                    n_ev, {k: float(v[b]) for k, v in rates_ps.items()})
                latency = float(acct["total_time_ms"])
                # Double-buffered acquisition: the uDMA acquires window
                # N+1 during processing of window N, so the sustained
                # period is max(window period, preprocessing + inference).
                proc_ms = (acct["stages"]["preprocessing"]["time_ms"]
                           + acct["stages"]["snn_inference"]["time_ms"])
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
        """Host-serializable checkpoint of one slot's carried state (the
        per-layer membrane planes), engine-agnostic through the serving
        layer's duck-typed probe; see :func:`export_state_slot`."""
        return export_state_slot(state, slot)

    def import_state(self, state, slot: int, payload):
        """Splice an exported carry back into row ``slot`` of a
        slot-major state; see :func:`import_state_slot`."""
        return import_state_slot(state, slot, payload)

    def infer(self, batch: ev.PaddedEventBatch, state=None):
        """Run a padded batch; returns one result per slot (None if empty).

        Synchronous convenience: dispatch + collect back to back. With
        ``state`` (slot-major carried-state pytree) returns
        ``(results, new_state)``; without it, just the results (the
        legacy stateless call, run from the zero state -- deprecated as
        a direct call form: pass ``init_state(batch_size)`` explicitly,
        or serve through ``StreamEngine.open(...)``).
        """
        if state is None:
            warn_deprecated_call(
                self, "stateless-infer",
                "stateless BatchedClosedLoop.infer(batch) is a legacy "
                "call form; pass carried state -- infer(batch, "
                "init_state(batch_size)) -- or serve windows through the "
                "session API: StreamEngine.open(...).submit(window)")
            return self.infer_collect(self.infer_dispatch(batch))
        pending, new_state = self.infer_dispatch(batch, state)
        return self.infer_collect(pending), new_state

    def infer_windows(self, windows: Sequence[Optional[ev.EventWindow]],
                      *, max_events: Optional[int] = None,
                      batch_size: Optional[int] = None,
                      duration_us: Optional[int] = None,
                      ) -> List[Optional[ClosedLoopResult]]:
        """Convenience: pad a window list and run it as one batch."""
        if not windows and not batch_size:
            return []
        if max_events is None:
            counts = [w.num_events for w in windows if w is not None]
            max_events = ev.next_pow2(max(counts)) if counts else ev.next_pow2(1)
        batch = ev.pad_event_windows(
            windows, max_events=max_events, batch_size=batch_size,
            duration_us=duration_us)
        # The B=1-style compat surface drives the stateless call form on
        # purpose; the deprecation nudge is for direct infer() callers.
        with suppress_api_deprecations():
            return self.infer(batch)


class ClosedLoopPipeline:
    """The paper's single-window loop: a B=1 view of the batched engine.

    Event counts are padded to power-of-two buckets so repeated calls with
    similar-sized windows reuse one compiled executable (padding does not
    change any result; voxel sums are exact).
    """

    def __init__(
        self,
        params,
        cfg,
        *,
        model: Optional[KrakenModel] = None,
        lif_scan_fn: Optional[Callable] = None,
        window_ms: float = 300.0,
        fuse_fc: bool = False,
    ):
        self.batched = BatchedClosedLoop(
            params, cfg, model=model, lif_scan_fn=lif_scan_fn,
            window_ms=window_ms, fuse_fc=fuse_fc)

    # Backwards-compatible attribute surface (pre-batched callers).
    params = property(lambda self: self.batched.params)
    cfg = property(lambda self: self.batched.cfg)
    model = property(lambda self: self.batched.model)
    window_ms = property(lambda self: self.batched.window_ms)
    plans = property(lambda self: self.batched.plans)
    fanouts = property(lambda self: self.batched.fanouts)

    def __call__(self, window: ev.EventWindow) -> ClosedLoopResult:
        return self.batched.infer_windows([window])[0]
