"""Multi-stream closed-loop control: many DVS sensors, one batched engine.

The ColibriUAV scenario scaled up: S independent event cameras (e.g. a
swarm of platforms, or several sensors on one platform) each produce 300 ms
windows; the StreamEngine serves them over a fixed number of batch slots,
so every engine step runs ONE jit'd closed-loop inference for a whole
batch of streams. Per-stream Kraken energy/latency accounting is identical
to running each window alone through ClosedLoopPipeline.

Streams are driven through the session-handle API: ``engine.open(...)``
returns a StreamHandle owning the stream's lifecycle (submit /
reset_state / checkpoint / close); ``engine.run()`` stays the completion
surface.

One stream ("tracker") is long-lived and STATEFUL: opened with
``stateful=True``, its LIF membranes carry across window boundaries --
the paper's continuous closed-loop regime -- while its neighbours stay
stateless. To make the carry visible, tracker and its stateless twin
receive the IDENTICAL event window every time: the twin's firing rates
are constant (each window starts from rest), the tracker's drift as the
carried membrane integrates evidence across windows.

Run:  PYTHONPATH=src python examples/multi_stream_control.py
"""
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import EngineConfig, init_snn
from repro.core import events as ev
from repro.core.pipeline import ClosedLoopPipeline
from repro.distributed import make_mesh
from repro.serving import StreamEngine

NUM_STREAMS = 6          # sensors
SLOTS = 4                # engine batch slots (< NUM_STREAMS: slots rotate)
WINDOWS_PER_STREAM = 5


def main():
    cfg = get_config("colibries", smoke=True)
    params = init_snn(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)

    # Each sensor performs its own gesture sequence.
    workload = {
        f"cam{s}": [ev.synthetic_gesture_events(
            rng, (s + k) % cfg.num_classes, mean_events=5000,
            height=cfg.height, width=cfg.width)
            for k in range(WINDOWS_PER_STREAM)]
        for s in range(NUM_STREAMS)
    }

    # One EngineConfig is the whole construction surface; mesh=make_mesh()
    # shards the slot axis over every local device (a 1-device mesh -- the
    # CPU default -- is served bitwise-identically to no mesh at all).
    engine = StreamEngine(params, cfg,
                          EngineConfig(max_streams=SLOTS, mesh=make_mesh()))
    # One handle per sensor: the session API latches modality (implicit
    # here -- single engine) and statefulness at open.
    handles = {sid: engine.open(stream_id=sid) for sid in workload}
    # Warm-up round: compiles the (SLOTS, max_events) closed-loop call.
    for sid, windows in workload.items():
        handles[sid].submit(windows[0])
    engine.run()
    warm = {sid: (st.windows, st.energy_mj, st.latency_ms_sum,
                  st.realtime_windows)
            for sid, st in engine.stream_stats.items()}
    warm_steps = engine.stats["steps"]
    warm_windows = engine.stats["windows"]

    for sid, windows in workload.items():
        for w in windows:
            handles[sid].submit(w)
    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0

    steps = engine.stats["steps"] - warm_steps
    occupancy = (engine.stats["windows"] - warm_windows) / steps
    print(f"{len(results)} windows from {NUM_STREAMS} streams over "
          f"{SLOTS} slots in {steps} steps "
          f"(mean occupancy {occupancy:.2f}) -> "
          f"{len(results) / wall:.0f} windows/s\n")

    print("stream  windows  mean_lat_ms  energy_mJ  mW_busy  realtime")
    for sid in sorted(engine.stream_stats):
        st = engine.stream_stats[sid]
        w0, e0, l0, r0 = warm[sid]      # exclude the warm-up round
        n = st.windows - w0
        lat = st.latency_ms_sum - l0
        energy = st.energy_mj - e0
        rt = (st.realtime_windows - r0) / n
        print(f"{sid:6s}  {n:7d}  {lat / n:11.2f}  {energy:9.3f}  "
              f"{energy / (lat * 1e-3):7.1f}  {rt:8.0%}")

    # -- stateful streaming: a long-lived stream whose membrane carries --
    # Same engine, same slots: "tracker" opts into carried state at
    # open, its "twin" does not; both see the identical window each time.
    tracker = engine.open(stream_id="tracker", stateful=True)
    twin = engine.open(stream_id="twin")
    repeated = ev.synthetic_gesture_events(
        rng, 3, mean_events=5000, height=cfg.height, width=cfg.width)
    for _ in range(WINDOWS_PER_STREAM):
        tracker.submit(repeated)
        twin.submit(repeated)
    drift = {"tracker": {}, "twin": {}}
    for r in engine.run():
        if r.stream_id in drift:
            drift[r.stream_id][r.seq] = r.result.breakdown["firing_rates"]

    print("\nstateful stream vs stateless twin (identical input window "
          "every time):\nwindow   twin fc1 rate   tracker fc1 rate   "
          "tracker drift vs window 0")
    base = drift["tracker"][0]["fc1"]
    for k in sorted(drift["tracker"]):
        tw, tr = drift["twin"][k]["fc1"], drift["tracker"][k]["fc1"]
        print(f"{k:6d}  {tw:14.4f}  {tr:17.4f}  {tr - base:+24.4f}")
    print("twin rates are constant (amnesiac windows); tracker rates "
          "move because\nits LIF membranes carry across windows "
          "(reset_state() would re-zero them).")

    # Looped baseline for comparison (same windows, one at a time).
    pipe = ClosedLoopPipeline(params, cfg)
    flat = [w for ws in workload.values() for w in ws]
    for w in flat[:3]:
        pipe(w)              # compile
    t0 = time.perf_counter()
    for w in flat:
        pipe(w)
    wall_loop = time.perf_counter() - t0
    print(f"\nlooped single-window baseline: "
          f"{len(flat) / wall_loop:.0f} windows/s "
          f"(batched speedup {wall_loop / wall:.2f}x)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
