"""End-to-end driver: STBP-train the paper's DVS-Gesture SCNN.

Reproduces the paper's training setup (Sec. III: STBP per Wu et al. 2018,
LIF dynamics matched to SNE) on synthetic DVS-Gesture-like event streams,
with the production trainer (checkpoint/restart, straggler tracking).
Defaults train the full 128x128 Table II network for a few hundred steps;
--smoke runs the reduced config for CI-speed validation.

Run:  PYTHONPATH=src python examples/train_dvs_gesture.py [--smoke]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import init_snn, snn_loss
from repro.core.pipeline import ClosedLoopPipeline
from repro.data import dvs_gesture_batch
from repro.training import checkpoint as CKPT
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="checkpoints/dvs_gesture")
    args = ap.parse_args()

    cfg = get_config("colibries", smoke=args.smoke)
    steps = args.steps or (40 if args.smoke else 300)
    batch = args.batch or (8 if args.smoke else 16)
    mean_events = 4000 if args.smoke else 60_000

    params = init_snn(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=steps,
                       weight_decay=1e-4)

    @jax.jit
    def step_fn(params, opt, vox, labels):
        (loss, aux), g = jax.value_and_grad(
            lambda p: snn_loss(p, vox, labels, cfg), has_aux=True)(params)
        params, opt, om = adamw_update(g, opt, params, ocfg)
        return params, opt, loss, aux["accuracy"], aux["firing_rates"]

    # resume if a checkpoint exists (fault tolerance)
    start = 0
    restored = CKPT.restore_latest(args.ckpt_dir,
                                   {"params": params, "opt": opt})
    if restored:
        start, state, extra = restored
        params, opt = state["params"], state["opt"]
        print(f"resumed from step {start}")

    accs = []
    for s in range(start, steps):
        b = dvs_gesture_batch(batch, s, height=cfg.height,
                              width=cfg.width, time_bins=cfg.time_bins,
                              mean_events=mean_events,
                              num_classes=cfg.num_classes)
        t0 = time.perf_counter()
        params, opt, loss, acc, rates = step_fn(params, opt, b.vox,
                                                b.labels)
        accs.append(float(acc))
        if (s + 1) % 10 == 0:
            r = {k: f"{float(v):.3f}" for k, v in rates.items()}
            print(f"step {s + 1:4d}  loss {float(loss):.4f}  "
                  f"acc {np.mean(accs[-10:]):.3f}  "
                  f"({(time.perf_counter() - t0) * 1e3:.0f} ms)  rates {r}")
        if (s + 1) % 50 == 0 or s + 1 == steps:
            CKPT.save_checkpoint(args.ckpt_dir, s + 1,
                                 {"params": params, "opt": opt})

    # Closed-loop evaluation with the trained net
    pipe = ClosedLoopPipeline(params, cfg)
    rng = np.random.default_rng(123)
    correct = 0
    n_eval = 20
    from repro.core import events as ev
    for i in range(n_eval):
        lab = int(rng.integers(0, cfg.num_classes))
        w = ev.synthetic_gesture_events(rng, lab, mean_events=mean_events,
                                        height=cfg.height, width=cfg.width,
                                        num_classes=cfg.num_classes)
        res = pipe(w)
        correct += int(res.label_pred[0]) == lab
    print(f"\nclosed-loop eval: {correct}/{n_eval} correct "
          f"(chance {1 / cfg.num_classes:.2f}); "
          f"latency {res.latency_ms:.1f} ms, energy {res.energy_mj:.2f} mJ,"
          f" realtime={res.realtime}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
