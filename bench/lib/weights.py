"""Seeded random weights, made on the device in one jitted call.

Each configuration's adapter (``bench/arch/<arch>.py``) says which
networks and layers it has; :func:`he_normal` makes them float32 and
He-initialised with a gain per network (a gain keeps deep spiking layers
firing with random weights).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Layers = Dict[str, Tuple[tuple, int]]   # layer -> (weight shape, fan-in)


def key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (wider than 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def he_normal(seed: int, nets: Dict[str, Tuple[float, Layers]]) -> dict:
    """``{net: {layer: {"w": array}}}`` for ``nets`` given as ``{net:
    (gain, {layer: (shape, fan_in)})}``: one key per network split from
    the seed's, one per layer split from the network's, in the order
    given; each weight normal with standard deviation
    ``gain * sqrt(2 / fan_in)``."""

    def init(k):
        out = {}
        for (name, (gain, layers)), kn in zip(
                nets.items(), jax.random.split(k, len(nets))):
            out[name] = {}
            for (layer, (shape, fan_in)), kl in zip(
                    layers.items(), jax.random.split(kn, len(layers))):
                std = gain * (2.0 / fan_in) ** 0.5
                out[name][layer] = {
                    "w": jax.random.normal(kl, shape, jnp.float32) * std}
        return out

    return jax.jit(init)(key(seed))
