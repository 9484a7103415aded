"""The CUTIE ternary CNN: ColibriES's frame-based inference network.

Kraken's second accelerator, CUTIE (Scherer et al., 2022), executes
fully-ternary CNNs: {-1, 0, +1} weights AND activations, unrolled ternary
MACs in silicon, with the final classifier kept full-precision. This module
builds that network in JAX on the repo's existing ternary substrate:

  * weights quantized with :func:`repro.core.ternary.ternarize` (TWN,
    per-output-channel scale),
  * the fully-connected layer stored 2-bit packed
    (:func:`repro.core.ternary.pack2bit`) and executed by the
    ``kernels/ternary_matmul`` Pallas kernel -- dequant-in-VMEM, the CUTIE
    weight-bandwidth win on TPU,
  * activations hard-ternarized between layers (CUTIE's ternary
    inter-layer format),
  * per-stream activation density reported alongside the logits: CUTIE's
    switching energy tracks non-zero operand activity, so the energy model
    (``KrakenModel.frame_loop``) charges each stream for its own activity,
    exactly as the SNN path charges per-stream firing rates.

The network family mirrors the Table II SCNN so the two wings are
comparable layer-for-layer (pool4 -> conv16 -> pool2 -> conv32 -> pool2 ->
fc -> classifier), just frame-in instead of spike-train-in.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.snn import HIGHEST
from repro.core.ternary import pack2bit, ternarize
from repro.kernels.ternary_matmul import ternary_matmul_pallas

__all__ = ["TCNConfig", "init_tcn", "pack_tcn", "tcn_apply",
           "tcn_layer_macs"]

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TCNConfig:
    """Configuration of the CUTIE ternary CNN (reduced variants for tests)."""

    height: int = 128
    width: int = 128
    in_channels: int = 1
    pool0: int = 4            # cluster-side downsampling before conv1
    conv1_features: int = 16
    conv2_features: int = 32
    hidden: int = 512
    num_classes: int = 11
    # Activation ternarization threshold (fraction of each layer's mean
    # absolute pre-activation); CUTIE's inter-layer format is ternary.
    act_threshold: float = 0.7
    init_gain: float = 1.0

    @property
    def post_pool0(self) -> Tuple[int, int]:
        return self.height // self.pool0, self.width // self.pool0

    @property
    def flat_dim(self) -> int:
        h, w = self.post_pool0
        return (h // 4) * (w // 4) * self.conv2_features

    def spatial_sizes(self):
        """(H, W, C) after each stage, for the MAC/energy accounting."""
        h0, w0 = self.post_pool0
        return {
            "input": (self.height, self.width, self.in_channels),
            "pool0": (h0, w0, self.in_channels),
            "conv1": (h0, w0, self.conv1_features),
            "pool1": (h0 // 2, w0 // 2, self.conv1_features),
            "conv2": (h0 // 2, w0 // 2, self.conv2_features),
            "pool2": (h0 // 4, w0 // 4, self.conv2_features),
            "fc1": (1, 1, self.hidden),
            "fc2": (1, 1, self.num_classes),
        }


def tcn_layer_macs(cfg: TCNConfig) -> Tuple[float, ...]:
    """Dense MAC count per CUTIE layer (conv1, conv2, fc1, fc2).

    CUTIE executes the full dense schedule every frame (no event sparsity
    in time), so latency is workload-*independent*; only switching energy
    varies with operand activity.
    """
    sizes = cfg.spatial_sizes()
    vol = lambda s: float(sizes[s][0] * sizes[s][1] * sizes[s][2])
    return (
        vol("conv1") * 9.0 * cfg.in_channels,
        vol("conv2") * 9.0 * cfg.conv1_features,
        float(cfg.flat_dim * cfg.hidden),
        float(cfg.hidden * cfg.num_classes),
    )


def init_tcn(rng: jax.Array, cfg: TCNConfig, dtype=jnp.float32) -> Params:
    """He-init the float (pre-quantization) TCN parameters."""
    k1, k2, k3, k4 = jax.random.split(rng, 4)

    def he(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype)
                * (cfg.init_gain * jnp.sqrt(2.0 / fan_in)).astype(dtype))

    return {
        "conv1": {"w": he(k1, (3, 3, cfg.in_channels, cfg.conv1_features),
                          9 * cfg.in_channels)},
        "conv2": {"w": he(k2, (3, 3, cfg.conv1_features, cfg.conv2_features),
                          9 * cfg.conv1_features)},
        "fc1": {"w": he(k3, (cfg.flat_dim, cfg.hidden), cfg.flat_dim)},
        "fc2": {"w": he(k4, (cfg.hidden, cfg.num_classes), cfg.hidden)},
    }


def pack_tcn(params: Params) -> Params:
    """Quantize float TCN params into CUTIE's deployment format.

    Conv kernels become {q int8, scale} pairs (TWN per-output-channel);
    fc1 becomes the 2-bit packed (K//4, N) layout the Pallas kernel
    consumes; the classifier (fc2) stays full-precision, as CUTIE does.
    """
    out: Params = {}
    for name in ("conv1", "conv2"):
        q, scale = ternarize(params[name]["w"], axis=-1)
        out[name] = {"q": q, "scale": scale}
    k, n = params["fc1"]["w"].shape
    if k % 4:
        raise ValueError(f"fc1 K={k} must be a multiple of 4 for packing")
    q, scale = ternarize(params["fc1"]["w"], axis=-1)   # scale (1, N)
    out["fc1"] = {"packed": pack2bit(q.T).T,            # (K//4, N) uint8
                  "scale": scale.reshape(n).astype(jnp.float32)}
    out["fc2"] = {"w": params["fc2"]["w"]}
    return out


def _avg_pool(x: jnp.ndarray, k: int) -> jnp.ndarray:
    return jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, k, k, 1), (1, k, k, 1), "VALID"
    ) / float(k * k)


def _ternary_conv(x: jnp.ndarray, layer: Params) -> jnp.ndarray:
    """SAME 3x3 conv with dequantized ternary weights (q * scale)."""
    w = layer["q"].astype(x.dtype) * layer["scale"].astype(x.dtype)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _ternarize_act(x: jnp.ndarray, threshold: float) -> jnp.ndarray:
    """CUTIE inter-layer format: hard-ternarize activations.

    Threshold is ``threshold * mean|x|`` per sample (reduced over every
    non-batch axis), so each batch row is ternarized independently --
    preserving the per-slot invariance the engine protocol relies on.
    """
    reduce_axes = tuple(range(1, x.ndim))
    delta = threshold * jnp.abs(x).mean(axis=reduce_axes, keepdims=True)
    return jnp.sign(x) * (jnp.abs(x) > delta).astype(x.dtype)


def tcn_apply(packed: Params, frames: jnp.ndarray, cfg: TCNConfig, *,
              ternary_matmul_fn=ternary_matmul_pallas,
              ) -> Dict[str, jnp.ndarray]:
    """Run the CUTIE TCN on normalized frames.

    Args:
      packed: deployment params from :func:`pack_tcn`.
      frames: (B, H, W, C) float frames in [-1, 1]
        (see :func:`repro.core.frames.normalize_frames`).
      ternary_matmul_fn: the packed fc1 GEMM, ``f(x, w_packed, scale)``;
        the Pallas kernel by default, ``kernels.ternary_matmul_ref`` for
        a plain reference.

    Returns:
      dict with ``logits`` (B, num_classes) and ``activity_per_stream`` --
      per-layer (B,) mean non-zero-activation densities, the operand
      activity that drives CUTIE's switching energy per stream.
    """
    # Per-stream density of non-zero ternary operands entering each layer.
    def density(s: jnp.ndarray) -> jnp.ndarray:
        axes = tuple(range(1, s.ndim))
        return (s != 0).astype(jnp.float32).mean(axis=axes)

    x0 = _avg_pool(frames, cfg.pool0)
    a1 = _ternary_conv(x0, packed["conv1"])
    s1 = _ternarize_act(a1, cfg.act_threshold)
    a2 = _ternary_conv(_avg_pool(s1, 2), packed["conv2"])
    s2 = _ternarize_act(a2, cfg.act_threshold)
    flat = _avg_pool(s2, 2).reshape(frames.shape[0], -1)
    # fc1 through the Pallas kernel: packed 2-bit weights dequantized in
    # VMEM (interpret mode off-TPU), f32 accumulation.
    h = ternary_matmul_fn(flat, packed["fc1"]["packed"],
                          packed["fc1"]["scale"])
    s3 = _ternarize_act(h, cfg.act_threshold)
    logits = jnp.matmul(s3, packed["fc2"]["w"], precision=HIGHEST)
    return {
        "logits": logits,
        "activity_per_stream": {
            "conv1": density(x0), "conv2": density(s1),
            "fc1": density(s2), "fc2": density(s3),
        },
    }
