"""Kernel micro-benchmarks (CPU wall time of interpret/jnp paths + the
structural VMEM/bandwidth accounting that motivates each kernel on TPU).

On this CPU container wall-clock numbers only sanity-check the harness;
the meaningful output is the bytes model: lif_scan's state-traffic saving
and ternary_matmul's 8x weight-byte reduction, both derived from shapes.

``stream_rows`` additionally measures closed-loop throughput (windows/s)
of the batched StreamEngine (fused fc kernels + pipelined step) against
the looped single-window pipeline at several batch sizes, and writes a
``BENCH_stream.json`` artifact; ``stateful_rows`` adds the stateful-vs-
stateless serving cell (carried LIF membranes on vs off, same engine) to
the same artifact; ``fusion_rows`` adds the cross-modal fusion cell
(FusionSession serving paired event+frame ticks through one engine vs
the two wings on separate engines); ``fleet_rows`` adds the fleet
control-plane cell (deadline-miss rate of a skewed two-engine fleet
with vs without the telemetry-driven rebalancer, plus live-migration
cost in ms); ``fault_rows`` adds the fault-tolerance cell (stateful
throughput at injected fault-rate 0 vs 5%, retry/quarantine counters,
median recovery cost in engine steps). ``hetero_rows`` measures the two
accelerator wings through the unified engine protocol -- event-SNN vs
frame-TCN throughput, alone and mixed in one engine -- and writes
``BENCH_hetero.json``.

Methodology (all rows): one dedicated warmup pass (compile + first
touch), then the median of 5 timed samples, each sample closed with
``jax.block_until_ready`` so async dispatch cannot leak device time out
of (or into) a sample. Medians make the committed artifacts stable
enough to gate on (see ``benchmarks/check_regression.py``).
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (EngineConfig, FrameTCNEngine, SNNConfig, TCNConfig,
                        init_snn, init_tcn)
from repro.core import events as ev
from repro.core import frames as fr
from repro.core.lif import LIFParams
from repro.core.pipeline import BatchedClosedLoop, ClosedLoopPipeline
from repro.fleet import CheckpointStore, FleetConfig, FleetRebalancer
from repro.kernels import (fc_lif_scan, lif_scan, lif_scan_ref,
                           pack_ternary_weights, ternary_matmul,
                           ternary_matmul_ref)
from repro.serving import DeadlinePolicy, FusionSession, StreamEngine

REPEATS = 5


def _time(fn, *args, iters=REPEATS):
    """Median-of-``iters`` wall time in us: one warmup call (compile +
    first touch), then every sample individually device-complete."""
    jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples)) * 1e6


def _median_throughput(measure, repeats=REPEATS):
    """Median windows/s over ``repeats`` full measurement passes."""
    return float(np.median([measure() for _ in range(repeats)]))


def lif_rows():
    p = LIFParams()
    rows = []
    for (t, n) in [(16, 32 * 32 * 16), (16, 2048), (32, 8 * 8 * 32)]:
        cur = jax.random.normal(jax.random.PRNGKey(0), (t, n)) * 0.8
        us_ref = _time(jax.jit(lambda c: lif_scan_ref(c, p)[0]), cur)
        us_k = _time(jax.jit(lambda c: lif_scan(c, p)[0]), cur)
        # HBM traffic model: reference scan writes/reads V (f32) every
        # step; fused kernel keeps V in VMEM.
        bytes_ref = t * n * (4 + 4 + 2 * 4)       # I read, S write, V rw
        bytes_fused = t * n * (4 + 4)             # I read, S write
        rows.append((f"lif_scan_T{t}_N{n}", us_k,
                     f"ref_us={us_ref:.0f};state_traffic_saving="
                     f"{bytes_ref / bytes_fused:.2f}x"))
    return rows


def ternary_rows():
    rows = []
    for (m, k, n) in [(1, 2048, 8192), (16, 4096, 4096)]:
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
        x = jax.random.normal(jax.random.PRNGKey(2), (m, k))
        wp, sc = pack_ternary_weights(w)
        us_ref = _time(jax.jit(ternary_matmul_ref), x, wp, sc)
        us_k = _time(ternary_matmul, x, wp, sc)
        w_bytes_bf16 = k * n * 2
        w_bytes_packed = (k // 4) * n + n * 4
        rows.append((f"ternary_mm_{m}x{k}x{n}", us_k,
                     f"ref_us={us_ref:.0f};weight_bytes="
                     f"{w_bytes_bf16 / w_bytes_packed:.2f}x_smaller"))
    return rows


def fc_fusion_rows():
    """The fused synapse+LIF fc path vs the unfused matmul + LIF-scan
    path, at the full Table II fc shapes. Wall time is CPU-interpret
    noise; the structural win is the eliminated current round-trip:
    unfused writes + re-reads the (T, B, N) f32 current tensor in HBM,
    fused consumes currents in-VMEM the step they are produced."""
    p = LIFParams()
    rows = []
    for (t, b, k, n) in [(16, 8, 2048, 512), (16, 8, 512, 11)]:
        s = (jax.random.uniform(jax.random.PRNGKey(0), (t, b, k))
             < 0.2).astype(jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) / np.sqrt(k)
        us_unfused = _time(
            jax.jit(lambda s, w: lif_scan(jnp.matmul(s, w), p)[0]), s, w)
        us_fused = _time(
            jax.jit(lambda s, w: fc_lif_scan(s, w, p)[0]), s, w)
        current_bytes = 2 * t * b * n * 4          # write + read back
        rows.append((f"fc_lif_fused_T{t}B{b}_{k}x{n}", us_fused,
                     f"unfused_us={us_unfused:.0f};hbm_current_traffic_"
                     f"eliminated={current_bytes / 1e6:.2f}MB"))
    return rows


def stream_rows(batch_sizes=(1, 2, 4, 8), windows_per_stream=16,
                repeats=REPEATS, out_json="BENCH_stream.json",
                fuse_fc=True, pipeline_depth=1):
    """Closed-loop throughput: looped single-window pipeline vs the batched
    StreamEngine at several batch sizes (B streams, fixed slots).

    The batched engine runs this PR's serving hot path: fused synapse+LIF
    fc kernels (``fuse_fc``) and the pipelined step (``pipeline_depth``).
    Each (b, side) cell gets a full warmup pass (compiles every shape
    bucket) up front; the ``repeats`` timed passes are then INTERLEAVED
    round-robin across every cell -- machine-speed drift over the bench's
    wall time lands evenly on all rows instead of skewing late rows
    against early ones -- and each cell reports its median."""
    cfg = SNNConfig(height=32, width=32, time_bins=8, conv1_features=4,
                    conv2_features=8, hidden=32, num_classes=11)
    params = init_snn(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    max_b = max(batch_sizes)
    windows = {
        s: [ev.synthetic_gesture_events(rng, (s + k) % 11, mean_events=3000,
                                        height=32, width=32)
            for k in range(windows_per_stream)]
        for s in range(max_b)
    }

    def looped_cell(b):
        pipe = ClosedLoopPipeline(params, cfg)
        work = [w for s in range(b) for w in windows[s]]
        for w in work:          # warm-up: compile every event bucket
            pipe(w)

        def measure():
            t0 = time.perf_counter()
            for w in work:
                pipe(w)
            return len(work) / (time.perf_counter() - t0)

        return measure

    def batched_cell(b):
        eng = StreamEngine(params, cfg, EngineConfig(
            max_streams=b, fuse_fc=fuse_fc,
            pipeline_depth=pipeline_depth))
        handles = {s: eng.open(stream_id=s) for s in range(b)}

        def submit_all():
            for s in range(b):
                for w in windows[s]:
                    handles[s].submit(w)

        submit_all()            # warm-up: compile the (B, bucket) shapes
        eng.run()

        def measure():
            submit_all()
            t0 = time.perf_counter()
            n = len(eng.run())
            return n / (time.perf_counter() - t0)

        return measure

    cells = {b: (looped_cell(b), batched_cell(b)) for b in batch_sizes}
    samples = {b: ([], []) for b in batch_sizes}
    for _ in range(repeats):
        for b in batch_sizes:
            looped, batched = cells[b]
            samples[b][0].append(looped())
            samples[b][1].append(batched())

    rows, artifact = [], []
    for b in batch_sizes:
        wps_loop = float(np.median(samples[b][0]))
        wps_batch = float(np.median(samples[b][1]))
        speedup = wps_batch / wps_loop
        rows.append((f"stream_closed_loop_B{b}", 1e6 / wps_batch,
                     f"batched_wps={wps_batch:.1f};looped_wps="
                     f"{wps_loop:.1f};speedup={speedup:.2f}x"))
        artifact.append({"batch_size": b,
                         "windows_per_stream": windows_per_stream,
                         "looped_windows_per_s": wps_loop,
                         "batched_windows_per_s": wps_batch,
                         "speedup": speedup})
    if out_json:
        with open(out_json, "w") as f:
            json.dump({"benchmark": "stream_closed_loop",
                       "config": "SNNConfig(32x32, T=8, reduced)",
                       "methodology": {
                           "warmup": "one full pass per (batch, side) cell",
                           "timing": f"median of {repeats} passes, "
                                     "interleaved round-robin across "
                                     "cells",
                           "batched_engine": {
                               "fuse_fc": fuse_fc,
                               "pipeline_depth": pipeline_depth,
                           },
                       },
                       "rows": artifact}, f, indent=2)
    return rows


def stateful_rows(batch_sizes=(1, 4, 8), windows_per_stream=16,
                  repeats=REPEATS, out_json="BENCH_stream.json",
                  fuse_fc=True, pipeline_depth=1):
    """Stateful vs stateless serving throughput (windows/s) at several
    batch sizes: the same StreamEngine hot path (fused fc, pipelined
    step), with every stream either carrying its LIF membranes across
    windows (``stateful=True``) or resetting per window (the default).

    The state plumbing is designed to be free on the hot path -- a lane
    with no stateful streams is served through the legacy stateless call
    forms untouched, and a stable stateful assignment takes the identity
    fast path -- so the ratio
    (stateful / stateless) should sit at ~1.0; the regression gate
    (``benchmarks/check_regression.py``) holds it above 0.95. Results
    are appended to the ``stream_rows`` artifact under
    ``stateful_rows``.
    """
    cfg = SNNConfig(height=32, width=32, time_bins=8, conv1_features=4,
                    conv2_features=8, hidden=32, num_classes=11)
    params = init_snn(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    max_b = max(batch_sizes)
    windows = {
        s: [ev.synthetic_gesture_events(rng, (s + k) % 11, mean_events=3000,
                                        height=32, width=32)
            for k in range(windows_per_stream)]
        for s in range(max_b)
    }

    def cell(b, stateful):
        eng = StreamEngine(params, cfg, EngineConfig(
            max_streams=b, fuse_fc=fuse_fc,
            pipeline_depth=pipeline_depth))
        handles = {s: eng.open(stream_id=s, stateful=stateful)
                   for s in range(b)}

        def submit_all():
            for s in range(b):
                for w in windows[s]:
                    handles[s].submit(w)

        submit_all()            # warm-up: compile the (B, bucket) shapes
        eng.run()

        def measure():
            submit_all()
            t0 = time.perf_counter()
            n = len(eng.run())
            return n / (time.perf_counter() - t0)

        return measure

    cells = {b: (cell(b, False), cell(b, True)) for b in batch_sizes}
    samples = {b: ([], []) for b in batch_sizes}
    for _ in range(repeats):
        for b in batch_sizes:
            stateless, stateful = cells[b]
            samples[b][0].append(stateless())
            samples[b][1].append(stateful())

    rows, artifact = [], []
    for b in batch_sizes:
        wps_less = float(np.median(samples[b][0]))
        wps_full = float(np.median(samples[b][1]))
        ratio = wps_full / wps_less
        rows.append((f"stream_stateful_B{b}", 1e6 / wps_full,
                     f"stateful_wps={wps_full:.1f};stateless_wps="
                     f"{wps_less:.1f};ratio={ratio:.3f}"))
        artifact.append({"batch_size": b,
                         "windows_per_stream": windows_per_stream,
                         "stateless_windows_per_s": wps_less,
                         "stateful_windows_per_s": wps_full,
                         "stateful_over_stateless": ratio})
    if out_json:
        try:
            with open(out_json) as f:
                doc = json.load(f)
        except FileNotFoundError:
            doc = {"benchmark": "stream_closed_loop"}
        doc["stateful_rows"] = artifact
        with open(out_json, "w") as f:
            json.dump(doc, f, indent=2)
    return rows


def fusion_rows(session_counts=(1, 2, 4), ticks_per_session=8,
                repeats=REPEATS, out_json="BENCH_stream.json"):
    """Cross-modal fusion throughput: fused event+frame streams (one
    FusionSession per sensor head, both wings in ONE StreamEngine with
    co-scheduled ticks, the cross-wing megastep, and depth-1 pipelined
    dispatch -- one fused jit'd call per step) vs the same workload with
    the two wings served SEPARATELY (an event-only and a frame-only
    engine run back to back, the pre-fusion serving shape). A tick = one
    event window + one frame window + the late-logit fuse; the ratio
    (fused / separate) is what the fusion fast path buys over decoupled
    wings and is gated by ``check_regression`` both against the baseline
    and with a runner-independent fresh-only floor (>= 1.1 at >= 2
    sessions). Swept over session counts; appended to the
    ``stream_rows`` artifact under ``fusion_rows``."""
    scfg = SNNConfig(height=32, width=32, time_bins=8, conv1_features=4,
                     conv2_features=8, hidden=32, num_classes=11)
    tcfg = TCNConfig(height=32, width=32, conv1_features=4,
                     conv2_features=8, hidden=32, num_classes=11)
    snn_params = init_snn(jax.random.PRNGKey(0), scfg)
    tcn_params = init_tcn(jax.random.PRNGKey(1), tcfg)
    rows, artifact = [], []
    for sessions in session_counts:
        rng = np.random.default_rng(0)
        ticks = {s: [(ev.synthetic_gesture_events(rng, (s + k) % 11,
                                                  mean_events=3000,
                                                  height=32, width=32),
                      fr.synthetic_gesture_frames(rng, (s + k) % 11,
                                                  height=32, width=32))
                     for k in range(ticks_per_session)]
                 for s in range(sessions)}
        n_ticks = sessions * ticks_per_session

        def fused_cell():
            eng = StreamEngine(
                engines=[BatchedClosedLoop(snn_params, scfg),
                         FrameTCNEngine(tcn_params, tcfg)],
                config=EngineConfig(max_streams=sessions, megastep=True,
                                    pipeline_depth=1))
            sess = {s: FusionSession(eng, session_id=f"head{s}")
                    for s in range(sessions)}

            def submit_all():
                for s in range(sessions):
                    for ev_w, fr_w in ticks[s]:
                        sess[s].submit(ev_w, fr_w)

            def drain_all():
                # One engine drain; rows routed across the sharing
                # sessions (each absorb() keeps its own rows, hands the
                # rest on).
                rows_ = eng.run()
                n = 0
                for s in sess.values():
                    rows_ = s.absorb(rows_)
                    n += len(s.drain())
                assert not rows_
                return n

            submit_all()        # warm-up: compile the fused megastep
            drain_all()

            def measure():
                submit_all()
                t0 = time.perf_counter()
                n = drain_all()
                assert n == n_ticks
                return n / (time.perf_counter() - t0)

            return measure

        def separate_cell():
            ev_eng = StreamEngine(
                engines=[BatchedClosedLoop(snn_params, scfg)],
                config=EngineConfig(max_streams=sessions))
            fr_eng = StreamEngine(
                engines=[FrameTCNEngine(tcn_params, tcfg)],
                config=EngineConfig(max_streams=sessions))
            ev_h = {s: ev_eng.open(stream_id=f"dvs{s}")
                    for s in range(sessions)}
            fr_h = {s: fr_eng.open(stream_id=f"cam{s}")
                    for s in range(sessions)}

            def submit_all():
                for s in range(sessions):
                    for ev_w, fr_w in ticks[s]:
                        ev_h[s].submit(ev_w)
                        fr_h[s].submit(fr_w)

            submit_all()        # warm-up
            ev_eng.run()
            fr_eng.run()

            def measure():
                submit_all()
                t0 = time.perf_counter()
                n = len(ev_eng.run())
                n_f = len(fr_eng.run())
                assert n == n_f == n_ticks
                return n / (time.perf_counter() - t0)

            return measure

        cells = (fused_cell(), separate_cell())
        samples = ([], [])
        for _ in range(repeats):
            samples[0].append(cells[0]())
            samples[1].append(cells[1]())

        tps_fused = float(np.median(samples[0]))
        tps_sep = float(np.median(samples[1]))
        ratio = tps_fused / tps_sep
        rows.append((f"stream_fusion_S{sessions}", 1e6 / tps_fused,
                     f"fused_tps={tps_fused:.1f};"
                     f"separate_tps={tps_sep:.1f};ratio={ratio:.3f}"))
        artifact.append({"sessions": sessions,
                         "ticks_per_session": ticks_per_session,
                         "separate_ticks_per_s": tps_sep,
                         "fused_ticks_per_s": tps_fused,
                         "fused_over_separate": ratio})
    if out_json:
        try:
            with open(out_json) as f:
                doc = json.load(f)
        except FileNotFoundError:
            doc = {"benchmark": "stream_closed_loop"}
        doc["fusion_rows"] = artifact
        with open(out_json, "w") as f:
            json.dump(doc, f, indent=2)
    return rows


def hetero_rows(slots=4, windows_per_stream=8,
                out_json="BENCH_hetero.json",
                stream_json="BENCH_stream.json"):
    """Unified-engine throughput: the event-SNN wing vs the frame-TCN wing
    (each alone on its own StreamEngine), and both mixed in one engine
    (one jit'd call per wing per step). ``mixed_over_serial`` compares
    the mixed engine against serving the same two-wing workload serially
    (the harmonic mean of the per-wing rates); it is folded into the
    ``BENCH_stream.json`` artifact as a ``hetero_rows`` cell so
    ``check_regression`` gates the mixed-fleet path."""
    scfg = SNNConfig(height=32, width=32, time_bins=8, conv1_features=4,
                     conv2_features=8, hidden=32, num_classes=11)
    tcfg = TCNConfig(height=32, width=32, conv1_features=4,
                     conv2_features=8, hidden=32, num_classes=11)
    snn_params = init_snn(jax.random.PRNGKey(0), scfg)
    tcn_params = init_tcn(jax.random.PRNGKey(1), tcfg)
    rng = np.random.default_rng(0)
    events = {s: [ev.synthetic_gesture_events(rng, (s + k) % 11,
                                              mean_events=3000,
                                              height=32, width=32)
                  for k in range(windows_per_stream)]
              for s in range(slots)}
    frames_ = {s: [fr.synthetic_gesture_frames(rng, (s + k) % 11,
                                               height=32, width=32)
                   for k in range(windows_per_stream)]
               for s in range(slots)}

    def run(engine_sets, submits):
        eng = StreamEngine(engines=engine_sets,
                           config=EngineConfig(max_streams=slots))
        handles = {sid: eng.open(modality=modality, stream_id=sid)
                   for sid, modality, _ in submits}

        def submit_all():
            for sid, _, ws in submits:
                for w in ws:
                    handles[sid].submit(w)

        submit_all()                          # warm-up: compile
        eng.run()

        def measure():
            submit_all()
            t0 = time.perf_counter()
            n = len(eng.run())
            return n / (time.perf_counter() - t0)

        return _median_throughput(measure)

    mk_event = lambda: BatchedClosedLoop(snn_params, scfg)
    mk_frame = lambda: FrameTCNEngine(tcn_params, tcfg)
    ev_subs = [(f"dvs{s}", "event", events[s]) for s in range(slots)]
    fr_subs = [(f"cam{s}", "frame", frames_[s]) for s in range(slots)]

    wps_event = run([mk_event()], ev_subs)
    wps_frame = run([mk_frame()], fr_subs)
    wps_mixed = run([mk_event(), mk_frame()], ev_subs + fr_subs)
    # Serving the mixed workload serially (all-event then all-frame)
    # moves windows at the harmonic mean of the per-wing rates; the
    # mixed engine should beat it by stepping both wings per round.
    wps_serial = 2.0 / (1.0 / wps_event + 1.0 / wps_frame)
    mixed_over_serial = wps_mixed / wps_serial

    rows = [
        ("hetero_event_snn", 1e6 / wps_event, f"wps={wps_event:.1f}"),
        ("hetero_frame_tcn", 1e6 / wps_frame, f"wps={wps_frame:.1f}"),
        ("hetero_mixed", 1e6 / wps_mixed,
         f"wps={wps_mixed:.1f};both_engines_per_step;"
         f"mixed_over_serial={mixed_over_serial:.3f}"),
    ]
    cell = {"slots_per_engine": slots,
            "windows_per_stream": windows_per_stream,
            "event_windows_per_s": wps_event,
            "frame_windows_per_s": wps_frame,
            "mixed_windows_per_s": wps_mixed,
            "mixed_over_serial": mixed_over_serial}
    if out_json:
        with open(out_json, "w") as f:
            json.dump({"benchmark": "hetero_engines", **cell}, f, indent=2)
    if stream_json:
        try:
            with open(stream_json) as f:
                doc = json.load(f)
        except FileNotFoundError:
            doc = {"benchmark": "stream_closed_loop"}
        doc["hetero_rows"] = [cell]
        with open(stream_json, "w") as f:
            json.dump(doc, f, indent=2)
    return rows


def fleet_rows(streams=4, windows_per_stream=6, repeats=REPEATS,
               out_json="BENCH_stream.json"):
    """Fleet control-plane cell: a deliberately skewed two-engine fleet
    (a hot 2-slot engine opens every deadlined stateful stream with all
    windows queued up front; a cold 4-slot engine idles) served twice
    under a shared logical clock -- static placement vs a
    ``FleetRebalancer`` live-migrating deep-queue streams hot-to-cold
    through the checkpoint store.

    Deadline-miss rates are measured on the logical clock (one tick per
    scheduling round), so they are DETERMINISTIC -- the regression gate
    checks ``rebalanced_miss_rate <= static_miss_rate`` on the fresh
    artifact alone. Wall-clock metrics (fleet windows/s and per-migration
    cost in ms) follow the usual methodology: one warmup pass per side,
    then ``repeats`` interleaved timed passes, medians reported; the
    rebalanced-over-static throughput ratio is the runner-independent
    fallback. Appended to the ``stream_rows`` artifact under
    ``fleet_rows``."""
    cfg = SNNConfig(height=32, width=32, time_bins=8, conv1_features=4,
                    conv2_features=8, hidden=32, num_classes=11)
    params = init_snn(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    windows = {
        f"p{s}": [ev.synthetic_gesture_events(rng, (s + k) % 11,
                                              mean_events=3000,
                                              height=32, width=32)
                  for k in range(windows_per_stream)]
        for s in range(streams)
    }
    n_total = streams * windows_per_stream

    def serve(rebalance):
        hot = StreamEngine(params, cfg, EngineConfig(
            max_streams=2, policy=DeadlinePolicy(fair_quantum=2)))
        cold = StreamEngine(params, cfg, EngineConfig(
            max_streams=4, policy=DeadlinePolicy(fair_quantum=2)))
        tick = [0]
        for e in (hot, cold):
            e.deadline_clock = lambda: float(tick[0])
        for sid in sorted(windows):
            h = hot.open(stream_id=sid, stateful=True)
            for k, w in enumerate(windows[sid]):
                h.submit(w, deadline=2.0 + 1.0 * k)
        reb = FleetRebalancer(
            {"hot": hot, "cold": cold}, store=CheckpointStore(),
            config=FleetConfig(imbalance=1.0, cooldown=1),
        ) if rebalance else None
        n = 0
        t0 = time.perf_counter()
        while hot.pending() or cold.pending():
            n += len(hot.step())
            n += len(cold.step())
            tick[0] += 1
            if reb is not None:
                n += len(reb.observe().displaced)
        wall = time.perf_counter() - t0
        assert n == n_total
        dated = missed = 0
        for e in (hot, cold):
            for st in e.stream_stats.values():
                dated += st.deadline_windows
                missed += st.deadline_missed
        mig_ms = [m.migration_ms for m in reb.migrations] if reb else []
        return n / wall, missed / dated, mig_ms

    serve(False)                 # warm-up: compile the hot lane's shapes
    serve(True)                  # warm-up: compile the cold lane's too
    s_static, s_rebal, mig_ms = [], [], []
    static_miss = rebal_miss = 0.0
    n_migrations = 0
    for _ in range(repeats):
        wps, static_miss, _ = serve(False)
        s_static.append(wps)
        wps, rebal_miss, ms = serve(True)
        s_rebal.append(wps)
        n_migrations = len(ms)
        mig_ms.extend(ms)

    wps_static = float(np.median(s_static))
    wps_rebal = float(np.median(s_rebal))
    ratio = wps_rebal / wps_static
    m_ms = float(np.median(mig_ms)) if mig_ms else 0.0
    rows = [(f"fleet_rebalance_S{streams}", 1e6 / wps_rebal,
             f"static_miss={static_miss:.3f};"
             f"rebalanced_miss={rebal_miss:.3f};"
             f"migration_ms={m_ms:.2f};migrations={n_migrations}")]
    artifact = [{"engines": 2, "streams": streams,
                 "windows_per_stream": windows_per_stream,
                 "static_miss_rate": static_miss,
                 "rebalanced_miss_rate": rebal_miss,
                 "static_windows_per_s": wps_static,
                 "rebalanced_windows_per_s": wps_rebal,
                 "rebalanced_over_static": ratio,
                 "migrations": n_migrations,
                 "migration_ms": m_ms}]
    if out_json:
        try:
            with open(out_json) as f:
                doc = json.load(f)
        except FileNotFoundError:
            doc = {"benchmark": "stream_closed_loop"}
        doc["fleet_rows"] = artifact
        with open(out_json, "w") as f:
            json.dump(doc, f, indent=2)
    return rows


def sharded_rows(device_counts=(1, 2, 4), slots=8, windows_per_stream=8,
                 repeats=REPEATS, out_json="BENCH_stream.json"):
    """Sharded serving throughput (windows/s) vs device count at B=8.

    Every cell runs in this process: the standard stateful pipelined
    workload with the slot axis sharded over ``make_mesh(d,
    devices=jax.devices()[:d])``, for each ``d`` in ``device_counts``
    that ``jax.devices()`` can give; ``devices=1`` is the baseline mesh.
    No child process is started, so the process that holds the chip is
    the one that measures it. On the CPU, force host devices for the
    whole process (``XLA_FLAGS=--xla_force_host_platform_device_count=4``)
    to get the D=2 and D=4 cells.

    CAVEAT (recorded in the artifact): forced host devices time-slice
    ONE physical CPU, so windows/s does not scale with d there -- the
    cell measures the sharded step's overhead (it must stay within tol
    of single-device), while real slot-axis scaling needs real devices.
    The regression gate holds each ``sharded_over_single`` ratio.
    """
    from repro.distributed import make_mesh

    cfg = SNNConfig(height=32, width=32, time_bins=8, conv1_features=4,
                    conv2_features=8, hidden=32, num_classes=11)
    params = init_snn(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    windows = {s: [ev.synthetic_gesture_events(rng, (s + k) % 11,
                                               mean_events=3000,
                                               height=32, width=32)
                   for k in range(windows_per_stream)]
               for s in range(slots)}
    devices = jax.devices()
    device_counts = [d for d in device_counts if d <= len(devices)]
    results = {}
    for d in device_counts:
        eng = StreamEngine(params, cfg, EngineConfig(
            max_streams=slots, fuse_fc=True, pipeline_depth=1,
            mesh=make_mesh(d, devices=devices[:d])))
        handles = {s: eng.open(stream_id=s, stateful=True)
                   for s in range(slots)}

        def submit_all():
            for s in range(slots):
                for w in windows[s]:
                    handles[s].submit(w)

        submit_all()
        eng.run()                           # warm-up: compile
        samples = []
        for _ in range(repeats):
            submit_all()
            t0 = time.perf_counter()
            n = len(eng.run())
            samples.append(n / (time.perf_counter() - t0))
        results[d] = {"windows_per_s": float(np.median(samples))}

    base_wps = results[min(device_counts)]["windows_per_s"]
    rows, artifact = [], []
    for d in device_counts:
        wps = results[d]["windows_per_s"]
        ratio = wps / base_wps
        rows.append((f"stream_sharded_D{d}", 1e6 / wps,
                     f"wps={wps:.1f};sharded_over_single={ratio:.3f};"
                     f"platform={devices[0].platform}"))
        artifact.append({"devices": d, "batch_size": slots,
                         "windows_per_stream": windows_per_stream,
                         "windows_per_s": wps,
                         "sharded_over_single": ratio})
    if out_json:
        try:
            with open(out_json) as f:
                doc = json.load(f)
        except FileNotFoundError:
            doc = {"benchmark": "stream_closed_loop"}
        doc["sharded_rows"] = artifact
        if devices[0].platform == "cpu":
            doc["sharded_caveat"] = (
                "forced host devices share one physical CPU: windows/s "
                "measures sharded-step overhead, not slot-axis scaling")
        with open(out_json, "w") as f:
            json.dump(doc, f, indent=2)
    return rows


def fault_rows(streams=2, windows_per_stream=8, fault_rate=0.05,
               repeats=REPEATS, out_json="BENCH_stream.json"):
    """Fault-tolerance cell: stateful serving throughput with the
    recovery layer on, at injected fault-rate 0 vs ``fault_rate``
    (seeded step errors through a :class:`~repro.fleet.faults.
    FaultInjector`), plus the median recovery cost in engine steps.

    The fault schedule is seeded and drawn in call order, and backoff
    counts logical engine steps, so the retry/quarantine counters and
    recovery-tick metrics are DETERMINISTIC on any runner -- the
    regression gate enforces them on the fresh artifact alone (>=1
    retry at 5%%, zero recovery events at 0%%). Wall-clock throughput
    follows the usual methodology (warmup, medians of ``repeats``),
    with the faulted-over-clean ratio as the runner-independent
    fallback. Appended to the ``stream_rows`` artifact under
    ``fault_rows``."""
    from repro.core._api import FaultConfig, RecoveryConfig
    from repro.fleet import FaultInjector

    cfg = SNNConfig(height=32, width=32, time_bins=8, conv1_features=4,
                    conv2_features=8, hidden=32, num_classes=11)
    params = init_snn(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    windows = {
        s: [ev.synthetic_gesture_events(rng, (s + k) % 11,
                                        mean_events=3000,
                                        height=32, width=32)
            for k in range(windows_per_stream)]
        for s in range(streams)
    }
    n_total = streams * windows_per_stream
    config = EngineConfig(
        max_streams=streams,
        recovery=RecoveryConfig(max_retries=4, backoff_steps=1,
                                dead_after=100))

    def serve(rate):
        inj = FaultInjector(FaultConfig(seed=7, step_error_rate=rate))
        eng = StreamEngine(
            engines=[inj.wrap(BatchedClosedLoop.from_config(
                params, cfg, config))],
            config=config)
        handles = {s: eng.open(modality="event", stream_id=s,
                               stateful=True)
                   for s in range(streams)}
        for s in range(streams):
            for w in windows[s]:
                handles[s].submit(w)
        # landed[(sid, seq)] = engine step at which the result emitted;
        # with the fault_log's per-failure steps this yields the
        # recovery cost of every retried window in logical steps.
        landed, step = {}, 0
        t0 = time.perf_counter()
        while eng.pending() or eng._inflight:
            step += 1
            for r in eng.step():
                if r.ok:
                    landed[(r.stream_id, r.seq)] = step
        wall = time.perf_counter() - t0
        assert len(landed) == n_total       # no quarantine at this seed
        first_fail = {}
        for f in eng.fault_log:
            if f["kind"] == "retry":
                first_fail.setdefault((f["stream"], f["seq"]), f["step"])
        recovery = [landed[k] - s for k, s in first_fail.items()]
        tel = eng.telemetry("event")
        return (n_total / wall, tel.retries, tel.quarantined,
                float(np.median(recovery)) if recovery else 0.0)

    serve(0.0)                       # warm-up: compile
    s_clean, s_fault = [], []
    retries = quarantined = 0
    recovery_ticks = 0.0
    for _ in range(repeats):
        wps, r0, q0, _ = serve(0.0)
        s_clean.append(wps)
        assert r0 == 0 and q0 == 0   # rate 0 engages no recovery
        wps, retries, quarantined, recovery_ticks = serve(fault_rate)
        s_fault.append(wps)

    wps_clean = float(np.median(s_clean))
    wps_fault = float(np.median(s_fault))
    ratio = wps_fault / wps_clean
    rows = [(f"fault_recovery_r{fault_rate:g}", 1e6 / wps_fault,
             f"clean_wps={wps_clean:.1f};faulted_wps={wps_fault:.1f};"
             f"retries={retries};recovery_ticks={recovery_ticks:.1f}")]
    artifact = [{"streams": streams,
                 "windows_per_stream": windows_per_stream,
                 "fault_rate": fault_rate,
                 "clean_windows_per_s": wps_clean,
                 "faulted_windows_per_s": wps_fault,
                 "faulted_over_clean": ratio,
                 "retries": int(retries),
                 "quarantined": int(quarantined),
                 "recovery_ticks_median": recovery_ticks}]
    if out_json:
        try:
            with open(out_json) as f:
                doc = json.load(f)
        except FileNotFoundError:
            doc = {"benchmark": "stream_closed_loop"}
        doc["fault_rows"] = artifact
        with open(out_json, "w") as f:
            json.dump(doc, f, indent=2)
    return rows


def main():
    for name, us, derived in (lif_rows() + ternary_rows() + fc_fusion_rows()
                              + stream_rows() + stateful_rows()
                              + fusion_rows() + fleet_rows()
                              + hetero_rows() + sharded_rows()
                              + fault_rows()):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
