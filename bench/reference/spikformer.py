"""Plain float32 reference of Spikformer-L-D on DVS windows, served closed
loop.

Written from the paper (Zhou et al., "Spikformer: When Spiking Neural
Network Meets Transformer", ICLR 2023, arXiv 2209.15425) and
spikingjelly's LIF node, with no kernels, no folding and nothing
imported from the program. Per time step t, for a batch of heads:

    SPS   4 x (conv3x3, no bias -> BN -> LIF -> maxpool 3x3/2, pad 1)
    RPE   x = x + LIF(BN(conv3x3(x)))
    block x = x + SSA(x);  x = x + MLP(x)
    SSA   Q, K, V = LIF(BN(x W)); per head A = (Q K^T) V * scale;
          LIF with v_th 0.5; LIF(BN(A W_p + b_p))
    MLP   LIF(BN(LIF(BN(x W_1 + b_1)) W_2 + b_2))
    head  logits = mean over t and tokens of x, then W_h . + b_h
    PWM   clip(0.5 + 0.5 * softmax(logits) @ M, 0, 1), M fixed

LIF, spikingjelly's multi-step node with decay_input and hard reset to
0, written literally: ``H = V + (X - V) / tau``, ``S = H >= v_th``,
``V = H (1 - S)``. The network is advanced time step by time step
through every layer; a stateful head's windows carry V over.

Departures from the paper:

* the input is the binary voxels of one 300 ms window (T bins, a voxel
  set by any event in it), not the paper's frames of event counts over
  a whole clip split into T parts;
* batch norm is its inference affine (``bn_scale``, ``bn_shift`` per
  channel), not batch statistics.

Convolutions and matmuls run at ``precision`` ("highest": full float32
on a TPU; "high", the control: operands rounded to two bfloat16 terms,
as ``bench/reference/scnn.py`` does).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.reference.scnn import HIGHEST, conv3x3, matmul, pwm, voxelize

__all__ = ["forward", "layers", "zero_state", "voxelize", "pwm"]


def layers(net: dict) -> Dict[str, tuple]:
    """Each LIF layer's (H, W, C) at its output, in execution order."""
    out = {}
    h, w = net["height"], net["width"]
    for i, c in enumerate(net["sps_channels"]):
        out[f"sps{i}"] = (h, w, c)
        h, w = h // 2, w // 2
    d = net["embed_dim"]
    out["rpe"] = (h, w, d)
    for j in range(net["depth"]):
        for n in ("q", "k", "v", "attn", "proj"):
            out[f"block{j}.{n}"] = (h, w, d)
        out[f"block{j}.mlp1"] = (h, w, d * net["mlp_ratio"])
        out[f"block{j}.mlp2"] = (h, w, d)
    return out


def zero_state(net: dict, batch: int) -> Dict[str, jnp.ndarray]:
    """Every LIF layer's membrane V at rest."""
    return {n: jnp.zeros((batch, *s), jnp.float32)
            for n, s in layers(net).items()}


def max_pool(x):
    """3x3 max pool, stride 2, padding 1, NHWC."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 ((0, 0), (1, 1), (1, 1), (0, 0)))


def forward(params, vox, net: dict, state: Optional[dict] = None,
            precision: str = "highest") -> dict:
    """One window for a batch of heads.

    ``vox``: (B, T, 2, H, W). Returns ``logits`` (B, K), ``label``,
    ``pwm``, ``counts`` -- each LIF layer's spike count per head, (B,
    layers) in :func:`layers` order -- and ``state``.
    """
    tau = net["tau"]
    b = vox.shape[0]
    d, heads = net["embed_dim"], net["num_heads"]
    names = list(layers(net))
    if state is None:
        state = zero_state(net, b)

    def bn(name, y):
        return y * params[name]["bn_scale"] + params[name]["bn_shift"]

    def lif(st, name, x, v_th):
        v = st[name].reshape(x.shape)           # tokens: (B, N, C)
        h = v + (x - v) / tau
        s = (h >= v_th).astype(jnp.float32)
        st[name] = (h * (1.0 - s)).reshape(st[name].shape)
        return s

    def linear(name, x):
        y = matmul(x, params[name]["w"], precision)
        if "b" in params[name]:
            y = y + params[name]["b"]
        return bn(name, y)

    def step(st, x_t):                            # x_t (B, 2, H, W)
        st = dict(st)
        out = {}
        x = jnp.transpose(x_t, (0, 2, 3, 1))      # NHWC
        for i in range(len(net["sps_channels"])):
            n = f"sps{i}"
            out[n] = lif(st, n, bn(n, conv3x3(x, params[n]["w"], precision)),
                         net["v_th"])
            x = max_pool(out[n])
        out["rpe"] = lif(st, "rpe",
                         bn("rpe", conv3x3(x, params["rpe"]["w"], precision)),
                         net["v_th"])
        x = (x + out["rpe"]).reshape(b, -1, d)    # (B, N, D) tokens
        for j in range(net["depth"]):
            p = f"block{j}."
            q, k, v = (lif(st, p + n, linear(p + n, x), net["v_th"])
                       for n in ("q", "k", "v"))
            split = lambda a: a.reshape(b, -1, heads, d // heads)
            att = jnp.einsum("bnhd,bmhd->bhnm", split(q), split(k),
                             precision=HIGHEST)
            a = jnp.einsum("bhnm,bmhd->bnhd", att, split(v),
                           precision=HIGHEST).reshape(b, -1, d)
            a = lif(st, p + "attn", a * net["attn_scale"], net["attn_v_th"])
            proj = lif(st, p + "proj", linear(p + "proj", a), net["v_th"])
            x = x + proj
            h = lif(st, p + "mlp1", linear(p + "mlp1", x), net["v_th"])
            m = lif(st, p + "mlp2", linear(p + "mlp2", h), net["v_th"])
            x = x + m
            out.update({p + "q": q, p + "k": k, p + "v": v, p + "attn": a,
                        p + "proj": proj, p + "mlp1": h, p + "mlp2": m})
        counts = jnp.stack([out[n].reshape(b, -1).sum(-1) for n in names],
                           -1)
        return st, (counts, x)

    state, (counts, xs) = jax.lax.scan(step, state,
                                       jnp.transpose(vox, (1, 0, 2, 3, 4)))
    feat = xs.mean(axis=(0, 2))                    # over t and tokens
    logits = matmul(feat, params["head"]["w"], precision) \
        + params["head"]["b"]
    return {"logits": logits, "label": jnp.argmax(logits, -1),
            "pwm": pwm(logits), "counts": counts.sum(axis=0),
            "state": state}
