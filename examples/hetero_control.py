"""Heterogeneous closed-loop control: event cameras AND frame cameras,
one engine-agnostic StreamEngine, both Kraken accelerator wings per step.

ColibriES's pitch is heterogeneity: DVS events route to the SNE (spiking
CNN), frames route to CUTIE (ternary CNN), over one shared FC + cluster
front end. This demo serves a mixed sensor fleet: each step() makes one
jit'd call per engine -- the event batch through the voxelize+SNN loop,
the frame batch through the normalize+TCN loop -- and every stream gets
its own wing-specific Kraken latency/energy breakdown. Urgent control
loops can ride the deadline-aware slot policy.

Run:  PYTHONPATH=src python examples/hetero_control.py
"""
import time

import jax
import numpy as np

from repro.configs.colibries import SMOKE, TCN_SMOKE
from repro.core import FrameTCNEngine, init_snn, init_tcn
from repro.core import events as ev
from repro.core import frames as fr
from repro.core.pipeline import BatchedClosedLoop
from repro.serving import DeadlinePolicy, StreamEngine

EVENT_STREAMS = 3
FRAME_STREAMS = 3
SLOTS = {"event": 2, "frame": 2}
WINDOWS_PER_STREAM = 4


def main():
    scfg, tcfg = SMOKE, TCN_SMOKE
    snn_params = init_snn(jax.random.PRNGKey(0), scfg)
    tcn_params = init_tcn(jax.random.PRNGKey(1), tcfg)
    rng = np.random.default_rng(7)

    engine = StreamEngine(
        engines=[BatchedClosedLoop(snn_params, scfg),
                 FrameTCNEngine(tcn_params, tcfg)],
        max_streams=SLOTS,
        policy=DeadlinePolicy(fair_quantum=2),
    )

    # A mixed fleet: DVS sensors (urgent flight loops, tight deadlines)
    # and frame cameras (slack monitoring loops). One handle per sensor:
    # modality is latched at open, deadlines ride each submit.
    handles = {f"dvs{s}": engine.open(modality="event",
                                      stream_id=f"dvs{s}")
               for s in range(EVENT_STREAMS)}
    handles.update({f"cam{s}": engine.open(modality="frame",
                                           stream_id=f"cam{s}")
                    for s in range(FRAME_STREAMS)})

    def submit_round(k):
        for s in range(EVENT_STREAMS):
            handles[f"dvs{s}"].submit(
                ev.synthetic_gesture_events(
                    rng, (s + k) % scfg.num_classes, mean_events=4000,
                    height=scfg.height, width=scfg.width),
                deadline=float(10 * k + s))
        for s in range(FRAME_STREAMS):
            handles[f"cam{s}"].submit(
                fr.synthetic_gesture_frames(
                    rng, (s + k) % tcfg.num_classes,
                    height=tcfg.height, width=tcfg.width),
                deadline=float(10 * k + 100 + s))

    submit_round(0)             # warm-up: compiles both engines' shapes
    engine.run()
    warm_windows = engine.stats["windows"]
    warm_steps = engine.stats["steps"]
    warm = {sid: (st.windows, st.energy_mj, st.latency_ms_sum)
            for sid, st in engine.stream_stats.items()}

    for k in range(WINDOWS_PER_STREAM):
        submit_round(k + 1)
    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0

    steps = engine.stats["steps"] - warm_steps
    served = engine.stats["windows"] - warm_windows
    n_event = sum(r.modality == "event" for r in results)
    n_frame = sum(r.modality == "frame" for r in results)
    print(f"{served} windows ({n_event} event + {n_frame} frame) over "
          f"{sum(SLOTS.values())} slots in {steps} steps -> "
          f"{served / wall:.0f} windows/s; one jit'd call per engine "
          f"per step\n")

    print("stream  wing   windows  mean_lat_ms  energy_mJ  engine_stage")
    for sid in sorted(engine.stream_stats):
        st = engine.stream_stats[sid]
        w0, e0, l0 = warm[sid]
        n = st.windows - w0
        wing = engine.modality_of(sid)
        stage = "snn_inference" if wing == "event" else "tcn_inference"
        print(f"{sid:6s}  {wing:5s}  {n:7d}  "
              f"{(st.latency_ms_sum - l0) / n:11.2f}  "
              f"{st.energy_mj - e0:9.3f}  {stage}")

    last = {r.stream_id: r.result for r in results}
    dvs, cam = last["dvs0"].breakdown, last["cam0"].breakdown
    print("\nper-window Kraken breakdowns (last window of each wing):")
    for name, bd in (("dvs0", dvs), ("cam0", cam)):
        stages = ", ".join(f"{s}={v['time_ms']:.2f}ms"
                           for s, v in bd["stages"].items())
        print(f"  {name}: {stages}; total {bd['total_energy_mj']:.3f} mJ")
    print(f"\ncompiled shapes: event={engine.compiled_shapes('event')} "
          f"frame={engine.compiled_shapes('frame')}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
