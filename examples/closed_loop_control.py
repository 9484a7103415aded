"""Closed-loop control demo: gesture -> setpoint tracking at 3.3 Hz.

Simulates the paper's target application (UAV-style closed-loop control):
a stream of 300 ms DVS windows drives the SNN classifier, whose PWM
outputs steer a toy first-order plant toward per-gesture setpoints. The
run reports control latency, per-window energy, and plant tracking error
-- the end-to-end story of Fig. 1 in the paper.

Run:  PYTHONPATH=src python examples/closed_loop_control.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.core import init_snn
from repro.core import events as ev
from repro.core.pipeline import ClosedLoopPipeline

PLANT_TAU = 0.8          # first-order plant time constant (windows)


def main():
    cfg = get_config("colibries", smoke=True)
    params = init_snn(jax.random.PRNGKey(0), cfg)
    pipe = ClosedLoopPipeline(params, cfg)
    rng = np.random.default_rng(7)

    # Gesture sequence the "pilot" performs; each class maps to a target
    # actuation vector via the same mixing matrix as pwm_from_logits.
    gestures = [1, 1, 4, 4, 4, 9, 9, 2, 2, 2]
    state = np.full(4, 0.5)
    total_energy = 0.0
    latencies, errors = [], []

    print("window  gesture  pred  latency_ms  energy_mJ  plant_state")
    for i, g in enumerate(gestures):
        w = ev.synthetic_gesture_events(rng, g, mean_events=5000,
                                        height=cfg.height,
                                        width=cfg.width)
        res = pipe(w)
        # first-order plant follows the PWM setpoint
        target = res.pwm[0]
        state = state + (target - state) * (1 - np.exp(-1 / PLANT_TAU))
        total_energy += res.energy_mj
        latencies.append(res.latency_ms)
        errors.append(float(np.abs(target - state).mean()))
        print(f"{i:6d}  {g:7d}  {int(res.label_pred[0]):4d}  "
              f"{res.latency_ms:10.2f}  {res.energy_mj:9.3f}  "
              f"{np.round(state, 3)}")

    print(f"\nmean control latency: {np.mean(latencies):.2f} ms "
          f"(paper full-scale: 164.5 ms)")
    avg_mw = total_energy / len(gestures) * 3.33   # mJ/window * windows/s
    print(f"energy for {len(gestures)} windows: {total_energy:.2f} mJ "
          f"(avg {avg_mw:.2f} mW; a 2 Wh battery sustains "
          f"{2000 / avg_mw:.0f} h of continuous 3.33 Hz control)")
    print(f"mean tracking error: {np.mean(errors):.3f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
