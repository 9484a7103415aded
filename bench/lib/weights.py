"""Seeded random weights, made on the device in one jitted call.

Float32, He-initialised with the configuration's ``init_gain`` (the
gain keeps the deep LIF layers firing at 10-30% with random weights).
Conv kernels are HWIO. The CUTIE weights are made in float32; the
program ternarizes and packs them itself, and the reference ternarizes
its own copy (``bench/reference/cutie.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (wider than 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _shapes(net: dict) -> dict:
    h0, w0 = net["height"] // net["pool0"], net["width"] // net["pool0"]
    flat = (h0 // 4) * (w0 // 4) * net["conv2_features"]
    return {
        "conv1": ((3, 3, net["in_channels"], net["conv1_features"]),
                  9 * net["in_channels"]),
        "conv2": ((3, 3, net["conv1_features"], net["conv2_features"]),
                  9 * net["conv1_features"]),
        "fc1": ((flat, net["hidden"]), flat),
        "fc2": ((net["hidden"], net["num_classes"]), net["hidden"]),
    }


def make(seed: int, snn: dict, tcn: dict | None = None):
    """``(snn_params, tcn_params or None)`` from the seed, on the device."""
    nets = {"snn": snn} if tcn is None else {"snn": snn, "tcn": tcn}

    def init(k):
        out = {}
        for (name, net), kn in zip(nets.items(),
                                   jax.random.split(k, len(nets))):
            layers = _shapes(net)
            out[name] = {}
            for (layer, (shape, fan_in)), kl in zip(
                    layers.items(), jax.random.split(kn, len(layers))):
                std = net["init_gain"] * (2.0 / fan_in) ** 0.5
                out[name][layer] = {
                    "w": jax.random.normal(kl, shape, jnp.float32) * std}
        return out

    params = jax.jit(init)(key(seed))
    return params["snn"], params.get("tcn")
