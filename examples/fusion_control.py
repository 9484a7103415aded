"""Cross-modal fusion control: one sensor head, both Kraken wings, one
actuation decision per control tick -- plus live stream migration.

The ColibriES headline scenario (as deployed on ColibriUAV): a combined
DVS + frame sensor head feeds the SNE (spiking CNN, event wing) and
CUTIE (ternary CNN, frame wing) in parallel; their classifier outputs
are fused late -- a convex combination of the two wings' logits -- into
a single PWM actuation per tick, with per-wing Kraken latency/energy
attribution.

Three session-API capabilities on display:

  * FusionSession -- one event handle + one frame handle bound into a
    single logical stream; each step still runs ONE jit'd call per
    engine lane, the session pairs the wings' results back up by tick.
  * checkpoint/restore -- mid-flight the whole (stateful) fusion stream
    is checkpointed into a host-serializable payload and restored into
    a BRAND-NEW StreamEngine, where the remaining ticks continue
    bitwise-identical to the uninterrupted run: stream migration
    between engine processes.
  * the fused fast path -- co-scheduled fusion ticks plus the
    cross-wing megastep (``EngineConfig(megastep=True)``) against the
    same workload on two decoupled single-wing engines: the demo times
    both and EXITS NONZERO if fused serving is slower, so the CI smoke
    job enforces the perf claim, not just the semantics.

Run:  PYTHONPATH=src python examples/fusion_control.py
"""
import pickle
import time

import jax
import numpy as np

from repro.configs.colibries import SMOKE, TCN_SMOKE
from repro.core import EngineConfig, FrameTCNEngine, init_snn, init_tcn
from repro.core import events as ev
from repro.core import frames as fr
from repro.core.pipeline import BatchedClosedLoop
from repro.serving import FusionSession, StreamEngine, late_logit_fusion

TICKS = 6
CUT = 3          # migrate the stream after this many ticks
HEADS = 2        # sensor heads in the timed fused-vs-separate race
REPEATS = 3


def make_engine(snn_params, tcn_params):
    """One StreamEngine serving both Kraken wings (fresh 'process')."""
    return StreamEngine(
        engines=[BatchedClosedLoop(snn_params, SMOKE),
                 FrameTCNEngine(tcn_params, TCN_SMOKE)],
        config=EngineConfig(max_streams={"event": 1, "frame": 1}),
    )


def sensor_head(rng, k):
    """One control tick's paired windows from the combined head."""
    label = k % SMOKE.num_classes
    return (ev.synthetic_gesture_events(rng, label, mean_events=4000,
                                        height=SMOKE.height,
                                        width=SMOKE.width),
            fr.synthetic_gesture_frames(rng, label, height=TCN_SMOKE.height,
                                        width=TCN_SMOKE.width))


def main():
    snn_params = init_snn(jax.random.PRNGKey(0), SMOKE)
    tcn_params = init_tcn(jax.random.PRNGKey(1), TCN_SMOKE)
    ticks = [sensor_head(np.random.default_rng(7), k)
             for k in range(TICKS)]

    # -- fused serving: one decision per tick ---------------------------
    session = FusionSession(make_engine(snn_params, tcn_params),
                            session_id="uav0", stateful=True,
                            fusion=late_logit_fusion(0.6, 0.4))
    for ev_w, fr_w in ticks:
        session.submit(ev_w, fr_w)
    fused = session.run()

    print("tick  pred  pwm[0..3]              mJ_event  mJ_frame  "
          "lat_ms  realtime")
    for r in fused:
        bd = r.result.breakdown
        pwm = "  ".join(f"{d:.3f}" for d in r.result.pwm[0])
        print(f"{r.seq:4d}  {int(r.result.label_pred[0]):4d}  {pwm}  "
              f"{bd['per_wing_energy_mj']['event']:8.3f}  "
              f"{bd['per_wing_energy_mj']['frame']:8.3f}  "
              f"{r.result.latency_ms:6.1f}  {r.result.realtime!s:>8}")
    st = session.stats
    print(f"\n{st['ticks_fused']} fused ticks "
          f"({st['event'].windows} event + {st['frame'].windows} frame "
          f"windows); rule = {session.fusion.name}; "
          f"wing energy split {st['event'].energy_mj:.2f} / "
          f"{st['frame'].energy_mj:.2f} mJ")

    # -- stream migration: checkpoint -> fresh engine -> restore --------
    part_a = FusionSession(make_engine(snn_params, tcn_params),
                           session_id="uav0", stateful=True,
                           fusion=late_logit_fusion(0.6, 0.4))
    for ev_w, fr_w in ticks[:CUT]:
        part_a.submit(ev_w, fr_w)
    migrated = part_a.run()

    blob = pickle.dumps(part_a.checkpoint())     # host-serializable
    part_b = FusionSession.restore(make_engine(snn_params, tcn_params),
                                   pickle.loads(blob),
                                   fusion=late_logit_fusion(0.6, 0.4))
    for ev_w, fr_w in ticks[CUT:]:
        part_b.submit(ev_w, fr_w)
    migrated += part_b.run()

    same = all(
        np.array_equal(a.result.pwm, b.result.pwm)
        and a.result.energy_mj == b.result.energy_mj
        for a, b in zip(fused, migrated))
    print(f"\nmigrated at tick {CUT} through a {len(blob)}-byte "
          f"checkpoint into a fresh engine: "
          f"{'bitwise-identical to the uninterrupted run' if same else 'MISMATCH'}")

    # -- the perf claim, enforced: fused must beat separate wings -------
    ratio = fused_vs_separate(snn_params, tcn_params)
    print(f"\nfused-vs-separate tick ratio over {HEADS} heads: "
          f"{ratio:.2f}x "
          f"({'fused serving is faster' if ratio >= 1.0 else 'FUSED IS SLOWER'})")
    if not (same and ratio >= 1.0):
        raise SystemExit(1)


def fused_vs_separate(snn_params, tcn_params):
    """Median fused/separate ticks-per-second over REPEATS interleaved
    passes: HEADS FusionSessions on one co-scheduled megastep engine vs
    the same windows through decoupled event-only + frame-only engines."""
    heads = {h: [sensor_head(np.random.default_rng(40 + h), k)
                 for k in range(TICKS)] for h in range(HEADS)}

    eng = StreamEngine(
        engines=[BatchedClosedLoop(snn_params, SMOKE),
                 FrameTCNEngine(tcn_params, TCN_SMOKE)],
        config=EngineConfig(max_streams=HEADS, megastep=True,
                            pipeline_depth=1))
    sess = {h: FusionSession(eng, session_id=f"head{h}")
            for h in range(HEADS)}

    def fused_pass():
        for h, tks in heads.items():
            for ev_w, fr_w in tks:
                sess[h].submit(ev_w, fr_w)
        t0 = time.perf_counter()
        rows = eng.run()
        n = 0
        for s in sess.values():
            rows = s.absorb(rows)
            n += len(s.drain())
        assert n == HEADS * TICKS and not rows
        return n / (time.perf_counter() - t0)

    ev_eng = StreamEngine(engines=[BatchedClosedLoop(snn_params, SMOKE)],
                          config=EngineConfig(max_streams=HEADS))
    fr_eng = StreamEngine(engines=[FrameTCNEngine(tcn_params, TCN_SMOKE)],
                          config=EngineConfig(max_streams=HEADS))
    ev_h = {h: ev_eng.open(stream_id=f"dvs{h}") for h in range(HEADS)}
    fr_h = {h: fr_eng.open(stream_id=f"cam{h}") for h in range(HEADS)}

    def separate_pass():
        for h, tks in heads.items():
            for ev_w, fr_w in tks:
                ev_h[h].submit(ev_w)
                fr_h[h].submit(fr_w)
        t0 = time.perf_counter()
        n = len(ev_eng.run()) + len(fr_eng.run())
        assert n == 2 * HEADS * TICKS
        return (n // 2) / (time.perf_counter() - t0)

    fused_pass(), separate_pass()            # warm-up: compile both sides
    fused, separate = [], []
    for _ in range(REPEATS):
        fused.append(fused_pass())
        separate.append(separate_pass())
    return float(np.median(fused) / np.median(separate))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
