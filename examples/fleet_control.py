"""Fleet control plane: autoscaling, live migration, and rebalancing.

One Kraken SoC closes one loop; a fleet serves thousands. This demo
drives the whole ``repro.fleet`` control plane over two event-wing
engine instances with a deliberately skewed load:

  * a **hot** engine (2 slots) opens four deadlined stateful streams
    with all their windows queued up front, plus ephemeral churn,
  * a **cold** engine (4 slots) sits nearly idle,
  * a :class:`~repro.fleet.autoscale.LaneAutoscaler` watches the hot
    lane's backlog telemetry and grows its slot count (recompile
    amortized through the AOT warmup cache),
  * a :class:`~repro.fleet.rebalance.FleetRebalancer` live-migrates
    deep-queue streams hot-to-cold through the checkpoint store, and
  * every migrated stream's results are checked bitwise against an
    uninterrupted single-engine run of the same windows.

Deadline misses are measured on a shared logical clock (one tick per
scheduling round), so the printout is deterministic.

Run:  PYTHONPATH=src python examples/fleet_control.py
"""
import jax
import numpy as np

from repro.configs.colibries import SMOKE
from repro.core import init_snn
from repro.core import events as ev
from repro.core._api import EngineConfig, FleetConfig
from repro.fleet import CheckpointStore, FleetRebalancer, LaneAutoscaler
from repro.serving import DeadlinePolicy, StreamEngine

N_STREAMS = 4
N_WINDOWS = 5


def windows_for(sid, n=N_WINDOWS):
    rng = np.random.default_rng(100 + int(sid[1:]))
    return [ev.synthetic_gesture_events(rng, k % SMOKE.num_classes,
                                        mean_events=3000,
                                        height=SMOKE.height,
                                        width=SMOKE.width)
            for k in range(n)]


def make_engine(params, slots):
    return StreamEngine(params, SMOKE, EngineConfig(
        max_streams=slots, policy=DeadlinePolicy(fair_quantum=2)))


def serve_fleet(params, streams, *, control):
    hot, cold = make_engine(params, 2), make_engine(params, 4)
    tick = [0]
    for eng in (hot, cold):
        eng.deadline_clock = lambda: float(tick[0])
    for sid in sorted(streams):
        h = hot.open(stream_id=sid, stateful=True)
        for k, w in enumerate(streams[sid]):
            h.submit(w, deadline=2.0 + 1.0 * k)
    scaler = reb = None
    if control:
        scaler = LaneAutoscaler(hot, config=FleetConfig(
            grow_backlog=3.0, grow_patience=2, max_slots=4))
        reb = FleetRebalancer(
            {"hot": hot, "cold": cold}, store=CheckpointStore(),
            config=FleetConfig(imbalance=1.0, cooldown=1))

    rows = []
    while hot.pending() or cold.pending():
        rows.extend(hot.step())
        rows.extend(cold.step())
        tick[0] += 1
        if scaler is not None:
            decision = scaler.observe()
            if decision.resized:
                print(f"  tick {tick[0]:2d}: autoscaler {decision.action} "
                      f"hot lane {decision.old_slots}->"
                      f"{decision.new_slots} ({decision.reason})")
        if reb is not None:
            report = reb.observe()
            rows.extend(report.displaced)
            for rec in report.moved:
                print(f"  tick {tick[0]:2d}: migrated {rec.stream_id!r} "
                      f"hot->cold in {rec.migration_ms:.1f} ms "
                      f"({len(rec.displaced)} displaced results)")
    dated = missed = 0
    for eng in (hot, cold):
        for st in eng.stream_stats.values():
            dated += st.deadline_windows
            missed += st.deadline_missed
    return rows, missed / dated


def main():
    params = init_snn(jax.random.PRNGKey(0), SMOKE)
    streams = {f"s{i}": windows_for(f"s{i}") for i in range(N_STREAMS)}

    # The oracle: each stream served alone, uninterrupted.
    oracle = {}
    for sid in sorted(streams):
        eng = make_engine(params, 2)
        h = eng.open(stream_id=sid, stateful=True)
        for w in streams[sid]:
            h.submit(w)
        for r in eng.run():
            oracle[(sid, r.seq)] = np.asarray(r.result.pwm)

    print("static fleet (no control plane):")
    _, static_miss = serve_fleet(params, streams, control=False)
    print(f"  deadline-miss rate: {static_miss:.1%}\n")

    print("controlled fleet (autoscaler + rebalancer):")
    rows, rebal_miss = serve_fleet(params, streams, control=True)
    print(f"  deadline-miss rate: {rebal_miss:.1%}")

    same = all(np.array_equal(np.asarray(r.result.pwm),
                              oracle[(r.stream_id, r.seq)])
               for r in rows)
    print(f"\nmiss rate {static_miss:.1%} -> {rebal_miss:.1%}; "
          f"migrated streams "
          f"{'bitwise-identical to uninterrupted runs' if same else 'MISMATCH'}")
    if not (same and rebal_miss <= static_miss):
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
