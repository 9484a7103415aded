"""Plain float32 reference of the CUTIE ternary CNN (the frame wing).

From the CUTIE description (ternary weights and activations, full
precision classifier), with dequantised weights and nothing imported
from the program:

    weights    TWN per output channel: delta = 0.7 mean|W|,
               q = sign(W) [|W| > delta], scale = mean of the kept |W|
    frame      pixels * 2/255 - 1
    pool 4x4 -> conv 3x3 (q*scale) -> ternarize -> pool 2x2 -> conv 3x3
    -> ternarize -> pool 2x2 -> fc1 (x @ q) * scale -> ternarize
    -> fc2 (float)
    ternarize  sign(a) [|a| > 0.7 mean|a| over the frame's activations]
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.scnn import conv3x3, matmul, pool


def ternarize_weights(w):
    """(q, scale) with the output channel on the last axis."""
    axes = tuple(range(w.ndim - 1))
    absw = jnp.abs(w)
    keep = absw > 0.7 * absw.mean(axis=axes, keepdims=True)
    scale = (jnp.where(keep, absw, 0.0).sum(axis=axes, keepdims=True)
             / jnp.maximum(keep.sum(axis=axes, keepdims=True), 1))
    return jnp.where(keep, jnp.sign(w), 0.0), scale


def ternarize_act(a, threshold: float):
    delta = threshold * jnp.abs(a).mean(axis=tuple(range(1, a.ndim)),
                                         keepdims=True)
    return jnp.sign(a) * (jnp.abs(a) > delta)


def forward(params, pixels, net: dict, precision: str = "highest"):
    """(B, H, W) uint8 frames -> (B, K) logits."""
    th = net["act_threshold"]
    x = (pixels.astype(jnp.float32) * (2.0 / 255.0) - 1.0)[..., None]
    q1, c1 = ternarize_weights(params["conv1"]["w"])
    q2, c2 = ternarize_weights(params["conv2"]["w"])
    q3, c3 = ternarize_weights(params["fc1"]["w"])
    s1 = ternarize_act(conv3x3(pool(x, net["pool0"]), q1 * c1, precision), th)
    s2 = ternarize_act(conv3x3(pool(s1, 2), q2 * c2, precision), th)
    flat = pool(s2, 2).reshape(x.shape[0], -1)
    s3 = ternarize_act(matmul(flat, q3, precision) * c3.reshape(-1), th)
    return matmul(s3, params["fc2"]["w"], precision)
