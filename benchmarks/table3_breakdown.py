"""Paper Table III: end-to-end closed-loop latency/energy breakdown.

Runs the actual pipeline (synthetic DVS window at the nominal event rate
-> voxelize -> Table II SCNN inference via the fused LIF path -> PWM) and
prints the per-stage time/power/energy table next to the paper's measured
values. The workload drivers (events, spike counts, TDM passes) come from
the simulation; the power/latency constants are the calibrated Kraken
model (core/energy.py).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import init_snn, NOMINAL, KrakenModel
from repro.core import events as ev
from repro.core.pipeline import ClosedLoopPipeline
from repro.kernels import lif_scan

PAPER = {
    "data_acquisition": (1.5, 0.006),
    "preprocessing": (131.0, 4.6),
    "snn_inference": (32.0, 1.4),
    "total": (164.5, 7.7),
}


def run(n_windows: int = 3, seed: int = 0):
    cfg = get_config("colibries")
    params = init_snn(jax.random.PRNGKey(seed), cfg)
    pipe = ClosedLoopPipeline(params, cfg,
                              lif_scan_fn=lif_scan)
    rng = np.random.default_rng(seed)
    rows = []
    t_wall = time.perf_counter()
    for i in range(n_windows):
        w = ev.synthetic_gesture_events(
            rng, int(rng.integers(0, 11)),
            mean_events=int(NOMINAL.events))
        res = pipe(w)
        rows.append(res)
    wall = time.perf_counter() - t_wall

    # aggregate modelled numbers across windows
    def stage(name, field):
        return float(np.mean([r.breakdown["stages"][name][field]
                              for r in rows]))

    out = []
    for name in ("data_acquisition", "preprocessing", "snn_inference"):
        t = stage(name, "time_ms")
        e = stage(name, "active_energy_mj")
        pt, pe = PAPER[name]
        out.append((name, t, e, pt, pe))
    tot_t = float(np.mean([r.latency_ms for r in rows]))
    tot_e = float(np.mean([r.energy_mj for r in rows]))
    out.append(("total", tot_t, tot_e, *PAPER["total"]))
    return out, rows, wall


def main():
    out, rows, wall = run()
    print("stage, model_time_ms, model_energy_mj, paper_time_ms, "
          "paper_energy_mj, ratio_t, ratio_e")
    for name, t, e, pt, pe in out:
        print(f"{name}, {t:.2f}, {e:.3f}, {pt}, {pe}, {t / pt:.2f}, "
              f"{e / pe:.2f}")
    print(f"# realtime (<=300ms window): "
          f"{all(r.realtime for r in rows)}; sustained "
          f"{rows[0].sustained_rate_hz:.2f} Hz; host wall {wall:.1f}s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
