"""Ablations over the paper's SNN design axes (beyond-paper analysis).

Sweeps (a) temporal resolution T, (b) surrogate width, (c) membrane leak
alpha on the reduced DVS-gesture task and reports end-of-training loss /
accuracy plus the modelled SNE latency (synops scale with T and firing
rate, so the energy model couples accuracy to milliwatts -- the trade the
paper's platform is built around).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core import SNNConfig, init_snn, snn_loss
from repro.core.lif import LIFParams
from repro.data import dvs_gesture_batch
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update


def _train(cfg: SNNConfig, steps: int = 25, batch: int = 8, seed: int = 0):
    params = init_snn(jax.random.PRNGKey(seed), cfg)
    opt = adamw_init(params)
    ocfg = AdamWConfig(lr=2e-3, warmup_steps=5, total_steps=steps,
                       weight_decay=0.0)

    @jax.jit
    def step(params, opt, vox, labels):
        (loss, aux), g = jax.value_and_grad(
            lambda p: snn_loss(p, vox, labels, cfg), has_aux=True)(params)
        params, opt, _ = adamw_update(g, opt, params, ocfg)
        return params, opt, loss, aux["accuracy"], aux["firing_rates"]

    losses, accs, rate = [], [], 0.0
    for s in range(steps):
        b = dvs_gesture_batch(batch, s, height=cfg.height, width=cfg.width,
                              time_bins=cfg.time_bins, mean_events=4000,
                              num_classes=cfg.num_classes)
        params, opt, loss, acc, rates = step(params, opt, b.vox, b.labels)
        losses.append(float(loss))
        accs.append(float(acc))
        rate = float(rates["conv1"])
    return np.mean(losses[-5:]), np.mean(accs[-5:]), rate


def main():
    base = SNNConfig(height=32, width=32, time_bins=8, conv1_features=4,
                     conv2_features=8, hidden=32, num_classes=4)
    print("ablation,setting,loss,acc,conv1_rate,rel_snn_latency")
    for t in (4, 8, 16):
        cfg = dataclasses.replace(base, time_bins=t)
        l, a, r = _train(cfg)
        # SNE latency ~ synops ~ T * rate (per energy model scaling law)
        print(f"time_bins,{t},{l:.3f},{a:.3f},{r:.3f},{t * r / (8 * 0.15):.2f}")
    for w in (1.0, 2.0, 4.0):
        cfg = dataclasses.replace(
            base, lif=dataclasses.replace(base.lif, surrogate_width=w))
        l, a, r = _train(cfg)
        print(f"surrogate_width,{w},{l:.3f},{a:.3f},{r:.3f},"
              f"{8 * r / (8 * 0.15):.2f}")
    for alpha in (0.5, 0.875, 1.0):
        cfg = dataclasses.replace(
            base, lif=dataclasses.replace(base.lif, alpha=alpha))
        l, a, r = _train(cfg)
        print(f"alpha,{alpha},{l:.3f},{a:.3f},{r:.3f},"
              f"{8 * r / (8 * 0.15):.2f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
