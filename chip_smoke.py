#!/usr/bin/env python3
"""Serve the paper's two networks end to end on a TPU and check the results.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # mesh-sharded serving on four chips

One chip: the Table II SCNN (``configs.colibries.CONFIG``) and the CUTIE
ternary CNN (``TCN_CONFIG``), with random weights made from ``--seed``,
are served through one ``StreamEngine`` with 8 slots per wing: six
stateful event streams and one stateful ``FusionSession`` (event + frame
wing), four 300 ms windows each, every window drawn from the seeded
synthetic DVS-Gesture generator at its default 60,000 events. The event
wing runs the Pallas LIF scan (conv1, conv2) and the fused fc+LIF scan
(fc1, fc2); the frame wing runs the packed ternary GEMM. What was served
is then compared with a plain float32 reference computed on the chip
from the same voxels and frames: the time-serial SCNN at "highest"
matmul precision, run as one uninterrupted 4*T-step scan per stream, and
the CUTIE network with the plain ``ternary_matmul_ref`` GEMM.

Four chips: the same streams through ``EngineConfig(mesh=make_mesh(4))``,
compared with the one-device engine in the same process; the carried
state and the step outputs must have shards on 4 distinct devices and the
compiled steps must hold no collectives.

Every line but the last reports a phase. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.
The exit code is 1 when a check fails or when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.colibries import CONFIG, TCN_CONFIG  # noqa: E402
from repro.core import (BatchedClosedLoop, EngineConfig,  # noqa: E402
                        FrameTCNEngine, SNNConfig, TCNConfig, init_snn,
                        init_tcn, snn_apply, snn_init_state,
                        tcn_apply)
from repro.core import events as ev  # noqa: E402
from repro.core import frames as fr  # noqa: E402
from repro.core.pipeline import pwm_from_logits  # noqa: E402
from repro.distributed import make_mesh  # noqa: E402
from repro.kernels import lif_scan, ternary_matmul_ref  # noqa: E402
from repro.kernels.backend import use_interpret  # noqa: E402
from repro.serving import FusionSession, StreamEngine  # noqa: E402

WINDOW_US = 300_000
N_PLAIN = 6                     # stateful event-only streams
N_WINDOWS = 4                   # windows per stream
SLOTS = 8
HEAD = "head"                   # the FusionSession (event + frame wing)
LAYERS = ("conv1", "conv2", "fc1", "fc2")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclasses.dataclass(frozen=True)
class Bounds:
    """How far served results may sit from the float32 reference.

    A LIF neuron is a threshold, so a current that differs in its last
    bits can flip a spike whose membrane sat on the threshold, and the
    flip carries forward through later timesteps and layers. On a TPU v5e
    the served path (convs and matmuls at HIGHEST precision) matched the
    reference exactly: every spike of every layer, label and PWM value.
    At the default precision, which rounds f32 weights to bf16 in the
    MXU, conv1 agreed on 0.99979 of its spikes, fc2 on 0.903, 21 of 28
    labels and PWM within 0.29. The bounds admit a rare summation-order
    tie and nothing near that.
    """

    # Fraction of spikes, per layer, equal to the reference's: at most
    # one flip in 10^4 (default precision gave 2.1e-4 in conv1 alone).
    spike_agreement: float = 0.9999
    # Fraction of windows whose label equals the reference's.
    label_agreement: float = 1.0
    # Largest |PWM - reference PWM|; one fc2 spike moves PWM by ~0.1.
    pwm_abs: float = 1e-4
    # Largest |firing rate - reference rate| over layers and windows:
    # under 0.2 of one fc2 spike (1/176) per stream-window.
    rate_abs: float = 1e-3


BOUNDS = Bounds()


class CheckFailed(AssertionError):
    pass


def log(phase: str, **kv) -> None:
    items = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase}] {items}", flush=True)


# ----------------------------------------------------------------------
# Traffic and engines
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Traffic:
    events: Dict[str, List[ev.EventWindow]]    # stream id -> windows
    frames: List[fr.FrameWindow]               # the head's frame wing

    @property
    def plain(self) -> List[str]:
        return [s for s in self.events if s != HEAD]


def make_traffic(seed: int, snn_cfg: SNNConfig, tcn_cfg: TCNConfig, *,
                 n_plain: int = N_PLAIN, n_windows: int = N_WINDOWS,
                 mean_events: int = 60_000) -> Traffic:
    """Seeded windows: ``n_plain`` event streams plus the fusion head."""
    rng = np.random.default_rng(seed)
    ids = [f"dvs{i}" for i in range(n_plain)] + [HEAD]
    events = {
        sid: [ev.synthetic_gesture_events(
            rng, (i + k) % snn_cfg.num_classes, duration_us=WINDOW_US,
            mean_events=mean_events, height=snn_cfg.height,
            width=snn_cfg.width, num_classes=snn_cfg.num_classes)
            for k in range(n_windows)]
        for i, sid in enumerate(ids)}
    frames = [fr.synthetic_gesture_frames(
        rng, k % tcn_cfg.num_classes, duration_us=WINDOW_US,
        height=tcn_cfg.height, width=tcn_cfg.width,
        num_classes=tcn_cfg.num_classes) for k in range(n_windows)]
    return Traffic(events, frames)


def build_engine(params, tcn_params, snn_cfg: SNNConfig, tcn_cfg: TCNConfig,
                 config: EngineConfig, lif_scan_fn: Callable) -> StreamEngine:
    event = BatchedClosedLoop.from_config(params, snn_cfg, config,
                                          lif_scan_fn=lif_scan_fn)
    frame = FrameTCNEngine.from_config(tcn_params, tcn_cfg, config)
    return StreamEngine(engines=[event, frame], config=config)


def shape_keys(traffic: Traffic, snn_cfg: SNNConfig, tcn_cfg: TCNConfig):
    """The one event and one frame executable key this traffic needs."""
    most = max(w.num_events for ws in traffic.events.values() for w in ws)
    return ((SLOTS, ev.next_pow2(most), WINDOW_US),
            (SLOTS, tcn_cfg.height, tcn_cfg.width, WINDOW_US))


@dataclasses.dataclass
class Served:
    # (stream id, seq) -> label (), pwm (C,), logits (K,), rates {layer: f}
    rows: Dict[tuple, dict]
    fused: Dict[int, dict]                     # head tick -> label, pwm


def serve(engine: StreamEngine, traffic: Traffic) -> Served:
    """Open every stream stateful, queue all windows, drain the engine."""
    handles = {sid: engine.open(modality="event", stream_id=sid,
                                stateful=True) for sid in traffic.plain}
    session = FusionSession(engine, session_id=HEAD, stateful=True)
    for k in range(len(traffic.frames)):
        for sid, h in handles.items():
            h.submit(traffic.events[sid][k])
        session.submit(traffic.events[HEAD][k], traffic.frames[k])
    ticks = session.run()
    rows, fused = {}, {}
    for r in session.unclaimed:
        if r.status != "ok":
            raise CheckFailed(f"stream {r.stream_id} window {r.seq} came "
                              f"back {r.status}: {r.error}")
        res = r.result
        rows[(r.stream_id, r.seq)] = dict(
            label=int(res.label_pred[0]), pwm=np.asarray(res.pwm[0]),
            logits=np.asarray(res.logits[0]),
            rates=dict(res.breakdown["firing_rates"]))
    for r in ticks:
        if r.status != "ok":
            raise CheckFailed(f"fusion tick {r.seq} came back {r.status}: "
                              f"{r.error}")
        fused[r.seq] = dict(label=int(np.asarray(r.result.label_pred)[0]),
                            pwm=np.asarray(r.result.pwm)[0],
                            logits=np.asarray(r.result.logits)[0])
    want = len(traffic.plain) * len(traffic.frames)
    if len(rows) != want or len(fused) != len(traffic.frames):
        raise CheckFailed(f"served {len(rows)}/{want} event windows and "
                          f"{len(fused)}/{len(traffic.frames)} fused ticks")
    return Served(rows, fused)


def kernel_calls(exe) -> int:
    """Mosaic kernel launches in a compiled executable."""
    return exe.as_text().count('custom_call_target="tpu_custom_call"')


# ----------------------------------------------------------------------
# The float32 reference and the comparison
# ----------------------------------------------------------------------

def voxels(traffic: Traffic, snn_cfg: SNNConfig) -> jnp.ndarray:
    """(S, W*T, 2, H, W) voxels of every event stream, windows concatenated
    along time, voxelized on the device exactly as the engine does."""
    ids = list(traffic.events)
    n_windows = len(traffic.events[ids[0]])
    vox_fn = jax.jit(lambda x, y, t, p, valid: ev.voxelize_batch(
        x, y, t, p, valid, duration_us=WINDOW_US,
        time_bins=snn_cfg.time_bins, height=snn_cfg.height,
        width=snn_cfg.width))
    per_window = []
    for k in range(n_windows):
        b = ev.pad_event_windows([traffic.events[s][k] for s in ids])
        per_window.append(vox_fn(b.x, b.y, b.t, b.p, b.valid))
    return jnp.concatenate(per_window, axis=1)


def reference_spikes(params, vox, snn_cfg: SNNConfig):
    """Per-layer (W*T, S, ...) spike trains of the time-serial SCNN at
    float32 ("highest") precision: one uninterrupted scan per stream."""
    fn = jax.jit(lambda p, v: snn_apply(p, v, snn_cfg,
                                        mode="time_serial")["spikes"])
    with jax.default_matmul_precision("highest"):
        return fn(params, vox)


def served_spikes(params, vox, snn_cfg: SNNConfig, lif_scan_fn: Callable,
                  n_windows: int):
    """The served event path (layer-serial, Pallas scans, fused fc) run
    window by window with carried state, per-layer spike trains joined
    along time to match :func:`reference_spikes`."""
    fn = jax.jit(lambda p, v, s: snn_apply(
        p, v, snn_cfg, mode="layer_serial", lif_scan_fn=lif_scan_fn,
        fuse_fc=True, state=s))
    t = snn_cfg.time_bins
    state = snn_init_state(snn_cfg, vox.shape[0])
    trains = []
    for k in range(n_windows):
        out = fn(params, vox[:, k * t:(k + 1) * t], state)
        trains.append(out["spikes"])
        state = out["state"]
    return {n: jnp.concatenate([tr[n] for tr in trains], axis=0)
            for n in LAYERS}


def frame_reference_logits(packed, traffic: Traffic, tcn_cfg: TCNConfig):
    pixels = fr.pad_frame_windows(
        traffic.frames, batch_size=len(traffic.frames),
        duration_us=WINDOW_US, height=tcn_cfg.height,
        width=tcn_cfg.width).pixels
    fn = jax.jit(lambda pk, px: tcn_apply(
        pk, fr.normalize_frames(px), tcn_cfg,
        ternary_matmul_fn=ternary_matmul_ref)["logits"])
    with jax.default_matmul_precision("highest"):
        return np.asarray(fn(packed, pixels))


def compare(served: Served, traffic: Traffic, ref: dict, ker: dict,
            frame_logits: np.ndarray, snn_cfg: SNNConfig,
            bounds: Bounds = BOUNDS) -> dict:
    """Measure the served results against the reference; raise
    :class:`CheckFailed` naming every bound that does not hold."""
    ids = list(traffic.events)
    t = snn_cfg.time_bins
    metrics = {}
    for n in LAYERS:
        metrics[f"spike_agreement_{n}"] = float(
            jnp.mean((ref[n] == ker[n]).astype(jnp.float32)))

    # Per-window reference readout: spike-count logits, labels, PWM and
    # per-layer firing rates, exactly as the engine reads its windows out.
    def window(k):
        sl = slice(k * t, (k + 1) * t)
        logits = ref["fc2"][sl].mean(axis=0) * 10.0          # (S, K)
        rates = {n: ref[n][sl].mean(axis=tuple(
            a for a in range(ref[n].ndim) if a != 1)) for n in LAYERS}
        return (np.asarray(logits), np.asarray(pwm_from_logits(logits)),
                {n: np.asarray(r) for n, r in rates.items()})

    labels_eq, pwm_err, logit_err, rate_err = [], 0.0, 0.0, 0.0
    for k in range(len(traffic.frames)):
        logits, pwm, rates = window(k)
        for sid in traffic.plain:
            i, row = ids.index(sid), served.rows[(sid, k)]
            labels_eq.append(row["label"] == int(np.argmax(logits[i])))
            pwm_err = max(pwm_err, float(np.abs(row["pwm"] - pwm[i]).max()))
            logit_err = max(logit_err,
                            float(np.abs(row["logits"] - logits[i]).max()))
            rate_err = max(rate_err, max(abs(row["rates"][n] - rates[n][i])
                                         for n in LAYERS))
        h = ids.index(HEAD)
        f_logits = 0.5 * logits[h] + 0.5 * frame_logits[k]
        f_pwm = np.asarray(pwm_from_logits(jnp.asarray(f_logits)))
        tick = served.fused[k]
        labels_eq.append(tick["label"] == int(np.argmax(f_logits)))
        pwm_err = max(pwm_err, float(np.abs(tick["pwm"] - f_pwm).max()))
        logit_err = max(logit_err,
                        float(np.abs(tick["logits"] - f_logits).max()))
    metrics.update(label_agreement=float(np.mean(labels_eq)),
                   windows=len(labels_eq), pwm_max_abs=pwm_err,
                   logit_max_abs=logit_err, rate_max_abs=rate_err)

    log("compare", **metrics)
    failed = []
    for n in LAYERS:
        got = metrics[f"spike_agreement_{n}"]
        if got < bounds.spike_agreement:
            failed.append(f"{n} spike agreement {got} < "
                          f"{bounds.spike_agreement}")
    if metrics["label_agreement"] < bounds.label_agreement:
        failed.append(f"label agreement {metrics['label_agreement']} < "
                      f"{bounds.label_agreement}")
    if pwm_err > bounds.pwm_abs:
        failed.append(f"PWM max abs {pwm_err} > {bounds.pwm_abs}")
    if rate_err > bounds.rate_abs:
        failed.append(f"firing-rate max abs {rate_err} > {bounds.rate_abs}")
    if failed:
        raise CheckFailed("; ".join(failed))
    return metrics


# ----------------------------------------------------------------------
# Phases
# ----------------------------------------------------------------------

class CacheEvents:
    """Counts JAX's persistent-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def one_chip(seed: int, snn_cfg: SNNConfig, tcn_cfg: TCNConfig, *,
             lif_scan_fn: Callable = lif_scan, mean_events: int = 60_000,
             n_plain: int = N_PLAIN, require_kernels: bool = True,
             cache: Optional[CacheEvents] = None) -> dict:
    """Build, serve, check against the reference. Returns the metrics."""
    t0 = time.perf_counter()
    k_snn, k_tcn = jax.random.split(jax.random.PRNGKey(seed))
    params = init_snn(k_snn, snn_cfg)
    tcn_params = init_tcn(k_tcn, tcn_cfg)
    traffic = make_traffic(seed, snn_cfg, tcn_cfg, n_plain=n_plain,
                           mean_events=mean_events)
    config = EngineConfig(max_streams=SLOTS, pipeline_depth=1,
                          recovery=None, fuse_fc=True)
    engine = build_engine(params, tcn_params, snn_cfg, tcn_cfg, config,
                          lif_scan_fn)
    n_events = [w.num_events for ws in traffic.events.values() for w in ws]
    log("build", seconds=round(time.perf_counter() - t0, 3),
        streams=len(traffic.events), windows=len(n_events),
        events_min=min(n_events), events_max=max(n_events))

    ev_key, fr_key = shape_keys(traffic, snn_cfg, tcn_cfg)
    timing = {}
    for modality, key in (("event", ev_key), ("frame", fr_key)):
        hits0, misses0 = (cache.hits, cache.misses) if cache else (0, 0)
        t0 = time.perf_counter()
        engine.warmup([key], modality=modality)
        timing[f"{modality}_seconds"] = round(time.perf_counter() - t0, 3)
        # A key compiled by an earlier run of this script comes out of the
        # persistent cache: hits and no misses.
        timing[f"{modality}_cache"] = (
            "off" if cache is None else
            "hit" if cache.misses == misses0 and cache.hits > hits0
            else "miss")
    log("compile", event_key=ev_key, frame_key=fr_key, **timing)

    ev_engine, fr_engine = engine.engines["event"], engine.engines["frame"]
    calls = {"event": kernel_calls(ev_engine._exe[ev_key]),
             "frame": kernel_calls(fr_engine._exe[fr_key])}
    log("kernels", interpret=use_interpret(),
        event_tpu_custom_calls=calls["event"],
        frame_tpu_custom_calls=calls["frame"])
    if require_kernels and (use_interpret() or calls["event"] < 4
                            or calls["frame"] < 1):
        raise CheckFailed(f"served steps must run compiled Pallas kernels "
                          f"(>= 4 in the event step, >= 1 in the frame "
                          f"step); interpret={use_interpret()}, {calls}")

    t0 = time.perf_counter()
    served = serve(engine, traffic)
    compiled = (ev_engine.compiled_shape_keys()
                | fr_engine.compiled_shape_keys())
    log("serve", seconds=round(time.perf_counter() - t0, 3),
        event_windows=len(served.rows) + len(served.fused),
        fused_ticks=len(served.fused), executables=len(compiled))
    if compiled != {ev_key, fr_key}:
        raise CheckFailed(f"serving compiled beyond the warmed keys: "
                          f"{sorted(compiled)}")

    t0 = time.perf_counter()
    vox = voxels(traffic, snn_cfg)
    ref = reference_spikes(params, vox, snn_cfg)
    ker = served_spikes(params, vox, snn_cfg, lif_scan_fn,
                        len(traffic.frames))
    frame_logits = frame_reference_logits(fr_engine.packed, traffic,
                                          tcn_cfg)
    jax.block_until_ready((ref, ker))
    log("reference", seconds=round(time.perf_counter() - t0, 3),
        voxels=tuple(vox.shape))

    return compare(served, traffic, ref, ker, frame_logits, snn_cfg)


def _sharded_on(arrays, n: int) -> bool:
    """Every array split over ``n`` distinct devices along its slot axis."""
    for a in jax.tree_util.tree_leaves(arrays):
        shards = a.addressable_shards
        devices = {s.device for s in shards}
        rows = {s.data.shape[0] for s in shards}
        if len(devices) != n or rows != {a.shape[0] // n}:
            return False
    return True


def four_chips(seed: int, snn_cfg: SNNConfig, tcn_cfg: TCNConfig, *,
               lif_scan_fn: Callable = lif_scan, mean_events: int = 60_000,
               n_devices: int = 4) -> dict:
    """Mesh-sharded serving against the one-device engine."""
    k_snn, k_tcn = jax.random.split(jax.random.PRNGKey(seed))
    params = init_snn(k_snn, snn_cfg)
    tcn_params = init_tcn(k_tcn, tcn_cfg)
    traffic = make_traffic(seed, snn_cfg, tcn_cfg, mean_events=mean_events)
    ev_key, fr_key = shape_keys(traffic, snn_cfg, tcn_cfg)
    base = EngineConfig(max_streams=SLOTS, pipeline_depth=1, recovery=None,
                        fuse_fc=True)
    mesh = make_mesh(n_devices)
    out = {}
    for name, config in (("one", base),
                         ("mesh", dataclasses.replace(base, mesh=mesh))):
        engine = build_engine(params, tcn_params, snn_cfg, tcn_cfg, config,
                              lif_scan_fn)
        t0 = time.perf_counter()
        engine.warmup([ev_key], modality="event")
        engine.warmup([fr_key], modality="frame")
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out[name] = (engine, serve(engine, traffic))
        log(f"serve_{name}", compile_seconds=round(compile_s, 3),
            serve_seconds=round(time.perf_counter() - t0, 3),
            devices=1 if config.mesh is None else n_devices)

    engine, sharded = out["mesh"]
    single = out["one"][1]
    labels_eq, pwm_err, logit_err = [], 0.0, 0.0
    for key, row in single.rows.items():
        got = sharded.rows[key]
        labels_eq.append(got["label"] == row["label"])
        pwm_err = max(pwm_err, float(np.abs(got["pwm"] - row["pwm"]).max()))
        logit_err = max(logit_err,
                        float(np.abs(got["logits"] - row["logits"]).max()))
    for seq, tick in single.fused.items():
        got = sharded.fused[seq]
        labels_eq.append(got["label"] == tick["label"])
        pwm_err = max(pwm_err, float(np.abs(got["pwm"] - tick["pwm"]).max()))

    ev_engine, fr_engine = engine.engines["event"], engine.engines["frame"]
    lane_state = engine._lanes["event"].state
    batch = ev_engine.prepare(
        [traffic.events[s][0] for s in traffic.events], batch_size=SLOTS)
    pending, new_state = ev_engine.infer_dispatch(
        batch, ev_engine.init_state(SLOTS))
    fr_pending = fr_engine.infer_dispatch(fr_engine.prepare(
        traffic.frames, batch_size=SLOTS))
    sharded_ok = (_sharded_on(lane_state, n_devices)
                  and _sharded_on((pending[1:], new_state), n_devices)
                  and _sharded_on(fr_pending[1:], n_devices))
    bad = [line.strip() for exe in (*ev_engine._exe.values(),
                                    *fr_engine._exe.values())
           for line in exe.as_text().splitlines()
           if any(c in line for c in COLLECTIVES)]
    metrics = dict(label_agreement=float(np.mean(labels_eq)),
                   windows=len(labels_eq), pwm_max_abs=pwm_err,
                   logit_max_abs=logit_err, sharded_on_all=sharded_ok,
                   collectives=len(bad))
    log("compare_mesh", **metrics)
    failed = []
    if metrics["label_agreement"] < 1.0:
        failed.append("sharded labels differ from the one-device engine")
    if pwm_err > BOUNDS.pwm_abs:
        failed.append(f"sharded PWM differs by {pwm_err} > {BOUNDS.pwm_abs}")
    if not sharded_ok:
        failed.append(f"state or outputs not split over {n_devices} devices")
    if bad:
        failed.append(f"compiled step holds collectives: {bad[:3]}")
    if failed:
        raise CheckFailed("; ".join(failed))
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve over a 4-device mesh and compare with one "
                         "device; runs nothing else")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found backend {backend!r} "
              f"with devices {jax.devices()}", file=sys.stderr)
        return 1
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    log("device", platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), cache_dir=enable_compile_cache())

    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args.seed, CONFIG, TCN_CONFIG)
        else:
            one_chip(args.seed, CONFIG, TCN_CONFIG, cache=CacheEvents())
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log("total", seconds=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
