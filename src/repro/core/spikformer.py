"""Spikformer (Zhou et al., "Spikformer: When Spiking Neural Network Meets
Transformer", ICLR 2023, arXiv 2209.15425) on DVS event windows.

The published DVS128 Gesture model is Spikformer-2-256 at T=16. Per time
step t, with binary input spikes (H x W x 2):

    SPS   four stages: conv3x3 (no bias) -> BN -> LIF -> maxpool 3x3/2
          (pad 1); channels 2 -> 32 -> 64 -> 128 -> 256, so 128x128 ->
          8x8, N = 64 tokens of D = 256
    RPE   x = x_feat + LIF(BN(conv3x3(x_feat)))
    L blocks   x = x + SSA(x);  x = x + MLP(x)
    SSA   Q, K, V = LIF(BN(x W_q,k,v));  A = (Q K^T) V * 0.125 per head
          (no softmax);  LIF_{v_th=0.5}(A);  LIF(BN(A W_p + b_p))
    MLP   LIF(BN(LIF(BN(x W_1 + b_1)) W_2 + b_2)), D -> 4D -> D
    head  logits = W_h . mean_{t, n} x + b_h

LIF is spikingjelly's multi-step node: tau = 2, decay_input, hard reset
to 0: ``H = V + (X - V) / tau``, ``S = H >= v_th``, ``V = H (1 - S)``.
That is the repo's ``V[t] = alpha V[t-1] (1 - S[t-1]) + I[t]`` with
``alpha = 1 - 1/tau`` and ``I = X / tau``, so the 1/tau folds into the
batch-norm scale and every LIF layer runs through the one LIF scan.

Served layer-serial like the SCNN: each layer takes the whole (T, ...)
train of its input, the T axis folded into the batch for the
convolutions and token linears. Batch norm is held as its inference
affine (``bn_scale``, ``bn_shift`` per channel) and folded, with 1/tau,
into a per-channel scale on the weights and a shift. Q, K and V are
binary, so ``(Q K^T) V * 0.125`` is exact in float32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.lif import LIFLayer, LIFParams, lif_scan_reference
from repro.core.snn import HIGHEST, _conv

__all__ = ["SpikformerConfig", "init_spikformer", "spikformer_apply"]

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SpikformerConfig:
    """Spikformer-L-D on DVS windows (defaults: Spikformer-2-256 on
    DVS128 Gesture; reduced variants for tests)."""

    height: int = 128
    width: int = 128
    in_channels: int = 2
    sps_channels: Tuple[int, ...] = (32, 64, 128, 256)
    num_heads: int = 16
    mlp_ratio: int = 4
    depth: int = 2
    num_classes: int = 11
    time_bins: int = 16
    tau: float = 2.0
    v_th: float = 1.0
    attn_v_th: float = 0.5
    attn_scale: float = 0.125
    bn_eps: float = 1e-5
    # He-normal gain of the random weights (tests; a benchmark
    # configuration makes its own weights).
    init_gain: float = 1.0

    @property
    def dim(self) -> int:
        return self.sps_channels[-1]

    @property
    def grid(self) -> Tuple[int, int]:
        """Token grid: each SPS stage halves the image."""
        k = len(self.sps_channels)
        return self.height >> k, self.width >> k

    @property
    def tokens(self) -> int:
        gh, gw = self.grid
        return gh * gw

    def lif(self, v_th: float) -> LIFParams:
        return LIFParams(alpha=1.0 - 1.0 / self.tau, v_th=v_th)

    # -- the event-network interface the serving engine drives ----------

    def lif_layers(self) -> Tuple[LIFLayer, ...]:
        """The LIF layers in execution order. A token layer's shape is its
        token grid by its width. The residual stream that feeds a block is
        credited to the layer that last wrote it."""
        out, src = [], None
        h, w = self.height, self.width
        for i, c in enumerate(self.sps_channels):
            out.append(LIFLayer(f"sps{i}", (h, w, c), 9.0 * c, src))
            src, h, w = f"sps{i}", h // 2, w // 2
        d, gh, gw = self.dim, *self.grid
        out.append(LIFLayer("rpe", (gh, gw, d), 9.0 * d, src))
        src = "rpe"
        hidden = d * self.mlp_ratio
        for j in range(self.depth):
            b = f"block{j}."
            out += [LIFLayer(b + n, (gh, gw, d), float(d), src)
                    for n in ("q", "k", "v")]
            out += [LIFLayer(b + "attn", (gh, gw, d), float(self.tokens),
                             b + "v"),
                    LIFLayer(b + "proj", (gh, gw, d), float(d), b + "attn"),
                    LIFLayer(b + "mlp1", (gh, gw, hidden), float(hidden),
                             b + "proj"),
                    LIFLayer(b + "mlp2", (gh, gw, d), float(d), b + "mlp1")]
            src = b + "mlp2"
        return tuple(out)

    def init_state(self, batch_size: int) -> Dict[str, jnp.ndarray]:
        """The zero carried state: one (B, H, W, C) membrane plane per LIF
        layer. Zero membrane is the cold start, so a call from it equals
        a stateless call."""
        return {l.name: jnp.zeros((batch_size, *l.shape), jnp.float32)
                for l in self.lif_layers()}

    def apply(self, params: Params, vox: jnp.ndarray, *, lif_scan_fn=None,
              fuse_fc: bool = False, state=None):
        """The served forward pass; returns ``(logits, per-stream firing
        rates, final state)``."""
        out = spikformer_apply(params, vox, self, lif_scan_fn=lif_scan_fn,
                               fuse_fc=fuse_fc, state=state)
        return out["logits"], out["firing_rates_per_stream"], out["state"]


def _linears(cfg: SpikformerConfig):
    """Token linear layers: name -> (K, N, has a bias)."""
    d, hidden = cfg.dim, cfg.dim * cfg.mlp_ratio
    out = {}
    for j in range(cfg.depth):
        b = f"block{j}."
        out.update({b + "q": (d, d, False), b + "k": (d, d, False),
                    b + "v": (d, d, False), b + "proj": (d, d, True),
                    b + "mlp1": (d, hidden, True),
                    b + "mlp2": (hidden, d, True)})
    return out


def init_spikformer(rng: jax.Array, cfg: SpikformerConfig) -> Params:
    """Random parameters: He-normal weights times ``init_gain`` (convs
    HWIO, linears (K, N)), PyTorch's uniform(+-1/sqrt(fan_in)) biases and
    batch norm at its initial affine (scale 1/sqrt(1 + eps), shift 0)."""
    convs = {}
    c_in = cfg.in_channels
    for i, c in enumerate(cfg.sps_channels):
        convs[f"sps{i}"] = (3, 3, c_in, c)
        c_in = c
    convs["rpe"] = (3, 3, cfg.dim, cfg.dim)
    linears = _linears(cfg)
    keys = iter(jax.random.split(rng, 2 * (len(convs) + len(linears)) + 2))

    def he(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * cfg.init_gain * (2.0 / fan_in) ** 0.5)

    def uniform(n, fan_in):
        return jax.random.uniform(next(keys), (n,), jnp.float32,
                                  -fan_in ** -0.5, fan_in ** -0.5)

    def bn(n):
        return {"bn_scale": jnp.full((n,), (1.0 + cfg.bn_eps) ** -0.5,
                                     jnp.float32),
                "bn_shift": jnp.zeros((n,), jnp.float32)}

    params = {}
    for name, shape in convs.items():
        params[name] = {"w": he(shape, 9 * shape[2]), **bn(shape[3])}
    for name, (k, n, bias) in linears.items():
        params[name] = {"w": he((k, n), k), **bn(n)}
        if bias:
            params[name]["b"] = uniform(n, k)
    params["head"] = {"w": he((cfg.dim, cfg.num_classes), cfg.dim),
                      "b": uniform(cfg.num_classes, cfg.dim)}
    return params


def _max_pool(x: jnp.ndarray) -> jnp.ndarray:
    """3x3 max pool, stride 2, padding 1, NHWC."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 ((0, 0), (1, 1), (1, 1), (0, 0)))


def _fold(p: Params, tau: float):
    """Batch norm and 1/tau as a per-output scale on the weights and a
    shift: ``BN(x W + b) / tau == x (W s) + b s + bn_shift / tau`` with
    ``s = bn_scale / tau``."""
    scale = p["bn_scale"] / tau
    shift = p["bn_shift"] / tau
    if "b" in p:
        shift = p["b"] * scale + shift
    return p["w"] * scale, shift


def spikformer_apply(
    params: Params,
    vox: jnp.ndarray,
    cfg: SpikformerConfig,
    *,
    lif_scan_fn=None,
    fuse_fc: bool = False,
    state: Dict[str, jnp.ndarray] | None = None,
) -> Dict[str, Any]:
    """Run Spikformer layer-serial on a voxelized spike batch.

    Args:
      vox: (B, T, 2, H, W) float spikes (from ``events.voxelize_batch``).
      lif_scan_fn: ``f(currents_T_first, LIFParams[, v0]) -> (spikes,
        v_final)`` for every LIF layer (e.g. the Pallas kernel); defaults
        to the pure-jnp reference.
      fuse_fc: run the token linears through the fused synapse+LIF
        kernel (:func:`repro.kernels.ops.fc_lif_scan`) instead of a
        matmul and ``lif_scan_fn``; q, k and v share one call.
      state: carried membranes (:meth:`SpikformerConfig.init_state`), or
        None to start from rest. Chaining windows through ``state`` equals
        one uninterrupted scan (the ``s0 = v0 >= v_th`` contract).

    Returns:
      dict with ``logits`` (B, classes), ``spikes`` -- each LIF layer's
      (T, B, H, W, C) train --, ``firing_rates_per_stream`` -- each
      layer's (B,) mean rate -- and ``state``, each layer's (B, H, W, C)
      final membrane.
    """
    scan = lif_scan_fn or lif_scan_reference
    b, t = vox.shape[0], vox.shape[1]
    shapes = {l.name: l.shape for l in cfg.lif_layers()}
    lif_p, attn_p = cfg.lif(cfg.v_th), cfg.lif(cfg.attn_v_th)
    spikes, finals = {}, {}

    def record(name, s, v):
        spikes[name] = s.reshape(t, b, *shapes[name])
        finals[name] = v.reshape(b, *shapes[name])

    def lif(name, cur, p):
        """LIF over (T*B, ...) or (T, B, ...) currents; (T*B, ...) spikes."""
        cur = cur.reshape(t, b, *shapes[name])
        s, vf = scan(cur, p) if state is None else scan(cur, p, state[name])
        record(name, s, vf)
        return s.reshape(t * b, *shapes[name])

    def linear_lif(names, x, p):
        """Token linear(s) + LIF over (T, B*N, K) inputs; the layers in
        ``names`` share the input and one call. (T, B*N, sum N) spikes."""
        ws, shifts = zip(*(_fold(params[n], cfg.tau) for n in names))
        w, shift = jnp.concatenate(ws, 1), jnp.concatenate(shifts)
        rows = x.shape[1]
        v = (None if state is None else jnp.concatenate(
            [state[n].reshape(rows, -1) for n in names], 1))
        if fuse_fc:
            # Lazy import: core -> kernels only on the fused path.
            from repro.kernels.ops import fc_lif_scan
            s, vf = fc_lif_scan(x, w, p, v, shift)
        else:
            cur = jnp.matmul(x, w, precision=HIGHEST) + shift
            s, vf = scan(cur, p) if v is None else scan(cur, p, v)
        lo = 0
        for n in names:
            hi = lo + params[n]["w"].shape[1]
            record(n, s[..., lo:hi], vf[:, lo:hi])
            lo = hi
        return s

    x = jnp.transpose(vox, (1, 0, 3, 4, 2))          # (T, B, H, W, C)
    x = x.reshape(t * b, *x.shape[2:])
    for i in range(len(cfg.sps_channels)):
        name = f"sps{i}"
        with jax.named_scope(name):
            w, shift = _fold(params[name], cfg.tau)
            x = _max_pool(lif(name, _conv(x, w) + shift, lif_p))
    with jax.named_scope("rpe"):
        w, shift = _fold(params["rpe"], cfg.tau)
        x = x + lif("rpe", _conv(x, w) + shift, lif_p)

    n, d, heads = cfg.tokens, cfg.dim, cfg.num_heads
    x = x.reshape(t, b * n, d)                       # token-major rows
    for j in range(cfg.depth):
        blk = f"block{j}."
        with jax.named_scope(f"block{j}"):
            with jax.named_scope("qkv"):
                qkv = linear_lif([blk + "q", blk + "k", blk + "v"], x, lif_p)
            with jax.named_scope("attn"):
                q, k, v = (a.reshape(t, b, n, heads, d // heads)
                           for a in jnp.split(qkv, 3, axis=-1))
                att = jnp.einsum("tbnhd,tbmhd->tbhnm", q, k,
                                 precision=HIGHEST)
                a = jnp.einsum("tbhnm,tbmhd->tbnhd", att, v,
                               precision=HIGHEST)
                a = lif(blk + "attn", a * (cfg.attn_scale / cfg.tau), attn_p)
            with jax.named_scope("proj"):
                x = x + linear_lif([blk + "proj"], a.reshape(t, b * n, d),
                                   lif_p)
            with jax.named_scope("mlp"):
                h = linear_lif([blk + "mlp1"], x, lif_p)
                x = x + linear_lif([blk + "mlp2"], h, lif_p)
    with jax.named_scope("head"):
        feat = x.reshape(t, b, n, d).mean(axis=(0, 2))  # exact: sum / 2^k
        logits = (jnp.matmul(feat, params["head"]["w"], precision=HIGHEST)
                  + params["head"]["b"])

    rates = {name: s.mean(axis=tuple(a for a in range(s.ndim) if a != 1))
             for name, s in spikes.items()}
    order = [l.name for l in cfg.lif_layers()]
    return {"logits": logits,
            "spikes": {k: spikes[k] for k in order},
            "firing_rates_per_stream": {k: rates[k] for k in order},
            "state": {k: finals[k] for k in order}}
