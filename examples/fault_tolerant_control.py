"""Fault-tolerant closed-loop control: degraded fusion + supervised
lane recovery, checked bitwise against the uninterrupted oracle.

ColibriUAV makes the ColibriES loop flight-critical: a corrupted or
missed inference is a control fault. This demo drives the stack's whole
fault-tolerance story on the headline two-wing scenario, in two acts:

  **Act 1 -- a wing dies mid-flight.** A seeded
  :class:`~repro.fleet.faults.FaultInjector` kills the frame wing (CUTIE)
  partway through a fused flight. The engine's recovery layer fail-fasts
  the dead lane; the :class:`~repro.serving.session.FusionSession` emits
  single-wing DEGRADED ticks on the surviving event wing (SNE) instead
  of stalling, until a fresh frame engine is installed
  (``replace_lane_engine``) and full fusion resumes. Every tick fused
  after the recovery is bitwise-identical to the uninterrupted run --
  the event wing's LIF carry never flinched.

  **Act 2 -- the stateful lane itself dies.** A
  :class:`~repro.fleet.supervisor.LaneSupervisor` journals every
  submission and auto-checkpoints the stream into a bounded
  :class:`~repro.fleet.store.CheckpointStore`. The injector kills the
  event lane mid-scan; the supervisor rebuilds it, restores the last
  checkpoint, and replays the journal -- and EVERY window, including the
  ones that failed while the lane was down, lands bitwise-identical to
  the uninterrupted scan.

Run:  PYTHONPATH=src python examples/fault_tolerant_control.py
"""
import jax
import numpy as np

from repro.configs.colibries import SMOKE, TCN_SMOKE
from repro.core import FrameTCNEngine, init_snn, init_tcn
from repro.core import events as ev
from repro.core import frames as fr
from repro.core._api import EngineConfig, FaultConfig, RecoveryConfig
from repro.core.pipeline import BatchedClosedLoop
from repro.fleet import CheckpointStore, FaultInjector, LaneSupervisor
from repro.serving import FusionSession, StreamEngine

TICKS = 8
KILL_AT = 3      # the frame wing dies dispatching this tick
REVIVE_AT = 6    # ...and a fresh engine is installed here

RECOVERY = RecoveryConfig(max_retries=0, backoff_steps=0, dead_after=1,
                          checkpoint_every=2)


def sensor_head(rng, k):
    label = k % SMOKE.num_classes
    return (ev.synthetic_gesture_events(rng, label, mean_events=4000,
                                        height=SMOKE.height,
                                        width=SMOKE.width),
            fr.synthetic_gesture_frames(rng, label, height=TCN_SMOKE.height,
                                        width=TCN_SMOKE.width))


def assert_bitwise(a, b):
    np.testing.assert_array_equal(a.label_pred, b.label_pred)
    np.testing.assert_array_equal(a.pwm, b.pwm)
    np.testing.assert_array_equal(a.logits, b.logits)


def act1_degraded_fusion(snn_params, tcn_params, ticks):
    print("== Act 1: frame wing dies mid-flight, fusion degrades ==")

    def make_session(inj):
        wrap = inj.wrap if inj else (lambda e: e)
        eng = StreamEngine(
            engines=[wrap(BatchedClosedLoop(snn_params, SMOKE)),
                     wrap(FrameTCNEngine(tcn_params, TCN_SMOKE))],
            config=EngineConfig(max_streams={"event": 1, "frame": 1},
                                recovery=RECOVERY))
        return eng, FusionSession(eng, session_id="uav0", stateful=True)

    # The oracle: the same flight with no faults.
    _, clean = make_session(None)
    for ev_w, fr_w in ticks:
        clean.submit(ev_w, fr_w)
    oracle = {r.seq: r.result for r in clean.run()}

    inj = FaultInjector(FaultConfig(seed=3))
    eng, sess = make_session(inj)
    rows = []
    for k, (ev_w, fr_w) in enumerate(ticks):
        if k == KILL_AT:
            inj.kill("frame")
            print(f"  tick {k}: frame wing KILLED")
        if k == REVIVE_AT:
            inj.revive("frame")
            eng.replace_lane_engine("frame", engine=inj.wrap(
                FrameTCNEngine(tcn_params, TCN_SMOKE)))
            print(f"  tick {k}: fresh frame engine installed")
        sess.submit(ev_w, fr_w)
        rows.extend(sess.step())
    sess.absorb(eng.flush())
    rows.extend(sess.drain())

    for r in rows:
        mark = {"ok": "fused", "degraded": "DEGRADED"}[r.status]
        extra = (f" (wing down: {r.result.breakdown['degraded_wing']})"
                 if r.status == "degraded" else "")
        print(f"  tick {r.seq}: {mark}  pred={int(r.result.label_pred[0])}"
              f"{extra}")
    assert [r.seq for r in rows] == list(range(TICKS))
    n_deg = sum(r.status == "degraded" for r in rows)
    assert n_deg == REVIVE_AT - KILL_AT, "wing-down stretch must degrade"
    # Bitwise: every FUSED tick -- before the kill and after the
    # recovery -- equals the uninterrupted flight (the event carry
    # never reset); degraded ticks equal the oracle's event wing.
    for r in rows:
        if r.status == "ok":
            assert_bitwise(r.result, oracle[r.seq])
    health = sess.wing_health()
    print(f"  {sess.ticks_fused} fused + {sess.ticks_degraded} degraded "
          f"ticks; frame wing failures seen: "
          f"{health['frame']['failures_seen']}")
    print("  bitwise: every fused tick == uninterrupted oracle  [OK]\n")


def act2_supervised_recovery(snn_params, ticks):
    print("== Act 2: stateful event lane dies, supervisor recovers ==")
    windows = [ev_w for ev_w, _ in ticks]
    config = EngineConfig(max_streams=1, recovery=RECOVERY)

    # The oracle: the same stateful scan with no faults.
    clean = StreamEngine(
        engines=[BatchedClosedLoop(snn_params, SMOKE)], config=config)
    ch = clean.open(modality="event", stream_id="imu", stateful=True)
    for w in windows:
        ch.submit(w)
    oracle = {r.seq: r.result for r in clean.run()}

    inj = FaultInjector(FaultConfig(seed=3))
    make = lambda: inj.wrap(BatchedClosedLoop(snn_params, SMOKE))
    eng = StreamEngine(engines=[make()], config=config)
    sup = LaneSupervisor(eng, store=CheckpointStore(capacity=4),
                         rebuild=lambda modality: make())
    sup.watch(eng.open(modality="event", stream_id="imu", stateful=True))
    got = []
    for k, w in enumerate(windows):
        if k == KILL_AT:
            inj.kill("event")
            print(f"  window {k}: event lane KILLED")
        if k == REVIVE_AT:
            inj.revive("event")
            print(f"  window {k}: injector revived (next rebuild sticks)")
        sup.submit("imu", w)
        got.extend(sup.tick(eng.step()))
    for _ in range(12):
        got.extend(sup.tick(eng.step()))

    ok = sorted((r for r in got if r.ok), key=lambda r: r.seq)
    failed = [r for r in got if not r.ok]
    assert [r.seq for r in ok] == list(range(TICKS)), \
        "every window must eventually succeed"
    for r in ok:
        assert_bitwise(r.result, oracle[r.seq])
    print(f"  {len(ok)}/{TICKS} windows served ok ({len(failed)} transient "
          f"failures while the lane was down); supervisor: "
          f"{sup.stats['restores']} restores, "
          f"{sup.stats['checkpoints']} checkpoints, "
          f"{sup.stats['replayed']} journal replays")
    print("  bitwise: every successful window == uninterrupted scan  [OK]")


def main():
    snn_params = init_snn(jax.random.PRNGKey(0), SMOKE)
    tcn_params = init_tcn(jax.random.PRNGKey(1), TCN_SMOKE)
    ticks = [sensor_head(np.random.default_rng(7), k)
             for k in range(TICKS)]
    act1_degraded_fusion(snn_params, tcn_params, ticks)
    act2_supervised_recovery(snn_params, ticks)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
