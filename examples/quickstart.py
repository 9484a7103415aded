"""Quickstart: the ColibriES pipeline in ~40 lines.

Builds the paper's Table II spiking CNN (reduced), voxelizes a synthetic
DVS gesture window, runs event->label->PWM through the closed loop with
the fused LIF Pallas kernel, and prints the modelled Kraken latency/energy
next to the paper's Table III.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.core import SNNConfig, init_snn
from repro.core import events as ev
from repro.core.pipeline import ClosedLoopPipeline
from repro.kernels import lif_scan


def main():
    # Reduced Table-II-family SCNN (full config: get_config("colibries")).
    cfg = get_config("colibries", smoke=True)
    params = init_snn(jax.random.PRNGKey(0), cfg)

    # One 300 ms DVS event window (synthetic gesture, class 7).
    rng = np.random.default_rng(0)
    window = ev.synthetic_gesture_events(
        rng, label=7, mean_events=6000,
        height=cfg.height, width=cfg.width)
    print(f"window: {window.num_events} events over "
          f"{window.duration_us / 1000:.0f} ms")

    # Closed loop: acquire -> preprocess -> SNE inference -> PWM.
    pipe = ClosedLoopPipeline(params, cfg,
                              lif_scan_fn=lif_scan)
    res = pipe(window)

    print(f"predicted class: {res.label_pred[0]}  (true: {window.label})")
    print(f"PWM duty cycles: {np.round(res.pwm[0], 3)}")
    print(f"modelled latency: {res.latency_ms:.2f} ms "
          f"(paper, full net: 164.5 ms)")
    print(f"modelled energy:  {res.energy_mj:.3f} mJ "
          f"(paper, full net: 7.7 mJ)")
    print(f"real-time at 300 ms windows: {res.realtime}; "
          f"sustained {res.sustained_rate_hz:.2f} Hz")
    for name, st in res.breakdown["stages"].items():
        print(f"  {name:18s} {st['time_ms']:8.2f} ms  "
              f"{st['active_energy_mj']:6.3f} mJ  [{st['domain']}]")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
