"""Plain float32 reference of the Table II SCNN, served closed loop.

Written from the paper's description and the LIF equations, with no
kernels, no batching tricks and nothing imported from the program:

    voxelize   300 ms of DVS events -> (T, 2, H, W) binary spikes
    pool 4x4 -> conv 3x3 (2->16) -> LIF -> pool 2x2 -> conv 3x3 (16->32)
    -> LIF -> pool 2x2 -> fc 2048->512 -> LIF -> fc 512->11 -> LIF
    logits     10 * (fc2 spike count / T)
    PWM        clip(0.5 + 0.5 * softmax(logits) @ M, 0, 1), M fixed

LIF (reset to zero, multiplicative leak), one time step:
    V[t] = alpha * V[t-1] * (1 - S[t-1]) + I[t];  S[t] = V[t] >= v_th

The network is advanced time step by time step through every layer
(the training view), and a stateful head's windows are one
uninterrupted scan: membranes and spikes carry over. Convolutions and
matmuls run at ``precision`` ("highest": full float32 on a TPU).
``precision="high"`` is the control: every operand is rounded to the
two bfloat16 terms that a three-pass bfloat16 product keeps, and the
product is then taken in full float32.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LAYERS = ("conv1", "conv2", "fc1", "fc2")


def round_operand(a: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``a`` as a product at ``precision`` sees it. ``"high"`` keeps the
    two bfloat16 terms (8 + 8 significant bits) of a three-pass product;
    ``reduce_precision`` rounds in a way the compiler may not fold away
    (a float32 -> bfloat16 -> float32 round trip, it may)."""
    if precision == "highest":
        return a
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    bf16 = lambda v: jax.lax.reduce_precision(v, exponent_bits=8,
                                              mantissa_bits=7)
    hi = bf16(a)
    return hi + bf16(a - hi)


def conv3x3(x, w, precision: str):
    """SAME 3x3 convolution, NHWC x HWIO."""
    return jax.lax.conv_general_dilated(
        round_operand(x, precision), round_operand(w, precision),
        (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)


def matmul(x, w, precision: str):
    return jnp.matmul(round_operand(x, precision),
                      round_operand(w, precision), precision=HIGHEST)


def pool(x, k: int):
    """k x k mean pool, stride k, NHWC."""
    return jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, k, k, 1),
                                 (1, k, k, 1), "VALID") / float(k * k)


def voxelize(x, y, t, p, valid, *, duration_us: int, time_bins: int,
             height: int, width: int):
    """(B, N) padded events -> (B, T, 2, H, W) spikes in {0, 1}: an event
    sets the voxel of its time bin, polarity and pixel."""
    bin_us = max(duration_us // time_bins, 1)
    tb = jnp.minimum(jnp.clip(t, 0, duration_us - 1) // bin_us,
                     time_bins - 1)

    def one(tb, p, y, x, valid):
        grid = jnp.zeros((time_bins, 2, height, width), jnp.float32)
        return grid.at[tb, p, y, x].add(valid.astype(jnp.float32))

    return jnp.minimum(jax.vmap(one)(tb, p, y, x, valid), 1.0)


def zero_state(net: dict, batch: int) -> Dict[str, jnp.ndarray]:
    """Membranes ``v*`` and spikes ``s*`` at rest."""
    h0, w0 = net["height"] // net["pool0"], net["width"] // net["pool0"]
    shapes = {"conv1": (h0, w0, net["conv1_features"]),
              "conv2": (h0 // 2, w0 // 2, net["conv2_features"]),
              "fc1": (net["hidden"],), "fc2": (net["num_classes"],)}
    st = {}
    for name, shape in shapes.items():
        st["v_" + name] = jnp.zeros((batch, *shape), jnp.float32)
        st["s_" + name] = jnp.zeros((batch, *shape), jnp.float32)
    return st


def pwm(logits):
    """The actuation map: 4 duty cycles in [0, 1] from the logits."""
    probs = jax.nn.softmax(logits, axis=-1)
    k = probs.shape[-1]
    mix = np.cos(np.arange(k)[:, None] * np.arange(1, 5)[None, :]
                 / k * np.pi).astype(np.float32)
    return jnp.clip(0.5 + 0.5 * (probs[..., :, None] * mix).sum(-2),
                    0.0, 1.0)


def forward(params, vox, net: dict, state: Optional[dict] = None,
            precision: str = "highest") -> dict:
    """One window for a batch of heads.

    ``vox``: (B, T, 2, H, W). Returns ``logits`` (B, K), ``label`` (B,),
    ``pwm`` (B, 4), ``counts`` -- each layer's spike count per head
    (B,) -- and ``state``, to carry into the head's next window.
    """
    alpha, v_th = net["lif_alpha"], net["lif_v_th"]
    b = vox.shape[0]
    if state is None:
        state = zero_state(net, b)
    w = {n: params[n]["w"] for n in LAYERS}

    def lif(st, name, current):
        v = alpha * st["v_" + name] * (1.0 - st["s_" + name]) + current
        s = (v >= v_th).astype(jnp.float32)
        st["v_" + name], st["s_" + name] = v, s
        return s

    def step(st, x_t):                          # x_t (B, 2, H, W)
        st = dict(st)
        x = jnp.transpose(x_t, (0, 2, 3, 1))    # NHWC
        s1 = lif(st, "conv1", conv3x3(pool(x, net["pool0"]), w["conv1"],
                                      precision))
        s2 = lif(st, "conv2", conv3x3(pool(s1, 2), w["conv2"], precision))
        flat = pool(s2, 2).reshape(b, -1)
        s3 = lif(st, "fc1", matmul(flat, w["fc1"], precision))
        s4 = lif(st, "fc2", matmul(s3, w["fc2"], precision))
        counts = [s.reshape(b, -1).sum(-1) for s in (s1, s2, s3, s4)]
        return st, (jnp.stack(counts, -1), s4)

    state, (counts, out) = jax.lax.scan(step, state,
                                        jnp.transpose(vox, (1, 0, 2, 3, 4)))
    logits = out.mean(axis=0) * 10.0
    return {"logits": logits, "label": jnp.argmax(logits, -1),
            "pwm": pwm(logits), "counts": counts.sum(axis=0),
            "state": state}


def layer_sizes(net: dict) -> Dict[str, int]:
    """Neurons per layer: the divisor that turns a firing rate averaged
    over time and neurons into a spike count."""
    h0, w0 = net["height"] // net["pool0"], net["width"] // net["pool0"]
    return {"conv1": h0 * w0 * net["conv1_features"],
            "conv2": (h0 // 2) * (w0 // 2) * net["conv2_features"],
            "fc1": net["hidden"], "fc2": net["num_classes"]}
