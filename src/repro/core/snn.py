"""The ColibriES DVS-Gesture spiking CNN (paper Table II) + STBP training.

Network (input 128x128x2 voxelized spikes, T timesteps):

    0 Input  128x128x2
    1 Pool   4x4 stride 4        -> 32x32x2
    2 Conv   3x3, 16 features    -> 32x32x16   + LIF
    3 Pool   2x2 stride 2        -> 16x16x16
    4 Conv   3x3, 32 features    -> 16x16x32   + LIF
    5 Pool   2x2 stride 2        -> 8x8x32
    6 Full   2048 -> 512                        + LIF
    7 Full   512  -> 11                         + LIF (spike-count readout)

Two mathematically equivalent execution orders are provided:

  * ``time_serial``  -- scan over T, all layers advanced per step (the STBP
    training view).
  * ``layer_serial`` -- each layer consumes the full (T, ...) spike train of
    its predecessor (the SNE hardware view: SNE executes one layer tile at a
    time in time-domain-multiplexed fashion; the cluster re-assembles the
    inter-layer spike streams). Because the network is feedforward and the
    dynamics causal, both orders produce identical spike trains -- this is
    asserted by tests and lets the fused Pallas ``lif_scan`` kernel be used
    per layer.

Training follows STBP (Wu et al., 2018), the method the paper derives its
training setup from: surrogate-gradient BPTT through the unrolled dynamics,
cross-entropy on spike-count logits.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.lif import (LIFLayer, LIFParams, lif_scan_reference,
                            lif_step, spike_surrogate)

__all__ = ["SNNConfig", "init_snn", "snn_init_state", "snn_apply",
           "snn_logits", "snn_loss", "SNN_STATE_LAYERS"]

Params = Dict[str, Any]

# The LIF layers whose membrane is carried state, in execution order. This
# names the leaves of the state pytree threaded through the serving stack
# (``snn_init_state`` / ``snn_apply(..., state=...)`` / the
# ``InferenceEngine`` state contract).
SNN_STATE_LAYERS = ("conv1", "conv2", "fc1", "fc2")


@dataclasses.dataclass(frozen=True)
class SNNConfig:
    """Configuration of the Table II SCNN (reduced variants for tests)."""

    height: int = 128
    width: int = 128
    in_channels: int = 2
    pool0: int = 4           # layer 1: 4x4 stride 4
    conv1_features: int = 16
    conv2_features: int = 32
    hidden: int = 512
    num_classes: int = 11
    time_bins: int = 16
    lif: LIFParams = LIFParams()
    readout: str = "spike_count"   # or "membrane"
    # Init gain keeps deep LIF layers out of the silent regime (synaptic
    # currents must reach v_th given sparse spike inputs); 2.0 with
    # v_th=0.5 / surrogate width 2.0 yields 10-30% firing rates at init.
    init_gain: float = 2.0

    @property
    def post_pool0(self) -> Tuple[int, int]:
        return self.height // self.pool0, self.width // self.pool0

    @property
    def flat_dim(self) -> int:
        h, w = self.post_pool0
        return (h // 4) * (w // 4) * self.conv2_features

    def spatial_sizes(self):
        """(H, W, C) after each stage, for the tiling planner / energy model."""
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

    # -- the event-network interface the serving engine drives ----------
    # (``BatchedClosedLoop`` takes its network from this config object.)

    def lif_layers(self) -> Tuple[LIFLayer, ...]:
        """The LIF layers in execution order (:class:`LIFLayer`)."""
        sizes = self.spatial_sizes()
        return (
            LIFLayer("conv1", sizes["conv1"], 9.0 * self.conv1_features,
                     None),          # 3x3 kernel into conv1 features
            LIFLayer("conv2", sizes["conv2"], 9.0 * self.conv2_features,
                     "conv1"),
            LIFLayer("fc1", sizes["fc1"], float(self.hidden), "conv2"),
            LIFLayer("fc2", sizes["fc2"], float(self.num_classes), "fc1"),
        )

    def init_state(self, batch_size: int) -> Dict[str, jnp.ndarray]:
        """The zero carried state; see :func:`snn_init_state`."""
        return snn_init_state(self, batch_size)

    def apply(self, params: Params, vox: jnp.ndarray, *, lif_scan_fn=None,
              fuse_fc: bool = False, state=None):
        """The served forward pass: ``snn_apply`` in ``layer_serial``
        mode, then the readout. Returns ``(logits, per-stream firing
        rates, final state)``."""
        out = snn_apply(params, vox, self, mode="layer_serial",
                        lif_scan_fn=lif_scan_fn, fuse_fc=fuse_fc,
                        state=state)
        with jax.named_scope("readout"):
            logits = snn_logits(out, self) * 10.0
        return logits, out["firing_rates_per_stream"], out["state"]


def init_snn(rng: jax.Array, cfg: SNNConfig, dtype=jnp.float32) -> Params:
    """He-init the SCNN parameters (conv kernels in HWIO layout)."""
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


def snn_init_state(cfg: SNNConfig, batch_size: int,
                   dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """The zero carried-state pytree for a batch of ``batch_size`` streams.

    One slot-major (B, ...) membrane plane per LIF layer
    (:data:`SNN_STATE_LAYERS`). Zero membrane is exactly the network's
    cold-start condition: ``snn_apply(..., state=snn_init_state(...))``
    is bitwise identical to ``snn_apply(..., state=None)``.
    """
    h0, w0 = cfg.post_pool0
    z = lambda *shape: jnp.zeros((batch_size, *shape), dtype)
    return {
        "conv1": z(h0, w0, cfg.conv1_features),
        "conv2": z(h0 // 2, w0 // 2, cfg.conv2_features),
        "fc1": z(cfg.hidden),
        "fc2": z(cfg.num_classes),
    }


def _avg_pool(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Average pool NHWC by k with stride k (SNN pooling on spike maps)."""
    return jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, k, k, 1), (1, k, k, 1), "VALID"
    ) / float(k * k)


# Synaptic currents are computed at full float32 precision. A LIF neuron
# is a threshold: on a TPU the default precision rounds f32 weights to
# bf16 in the MXU, and the flipped spikes compound through time and
# layers (served vs the float32 reference on a TPU v5e: fc2 spike
# agreement 0.90, 21 of 28 labels; at HIGHEST: identical).
HIGHEST = jax.lax.Precision.HIGHEST


def _conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """SAME 3x3 conv, NHWC x HWIO -> NHWC."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _currents_fn(params: Params, cfg: SNNConfig):
    """Per-stage synaptic-current functions (spikes -> currents)."""

    def i1(x_t):  # (B,H,W,2) input spikes -> conv1 currents
        return _conv(_avg_pool(x_t, cfg.pool0), params["conv1"]["w"])

    def i2(s1):   # conv1 spikes -> conv2 currents
        return _conv(_avg_pool(s1, 2), params["conv2"]["w"])

    def i3(s2):   # conv2 spikes -> fc1 currents
        pooled = _avg_pool(s2, 2)
        return jnp.matmul(pooled.reshape(pooled.shape[0], -1),
                          params["fc1"]["w"], precision=HIGHEST)

    def i4(s3):   # fc1 spikes -> fc2 currents
        return jnp.matmul(s3, params["fc2"]["w"], precision=HIGHEST)

    return i1, i2, i3, i4


def snn_apply(
    params: Params,
    vox: jnp.ndarray,
    cfg: SNNConfig,
    *,
    mode: str = "time_serial",
    lif_scan_fn=None,
    fuse_fc: bool = False,
    fc_lif_scan_fn=None,
    state: Dict[str, jnp.ndarray] | None = None,
) -> Dict[str, jnp.ndarray]:
    """Run the SCNN on a voxelized spike batch.

    Args:
      params: from ``init_snn``.
      vox: (B, T, 2, H, W) float spikes (from ``events.voxelize_batch``).
      mode: ``time_serial`` (STBP view) or ``layer_serial`` (SNE view).
      lif_scan_fn: optional fused scan ``f(currents_T_first, LIFParams[,
        v0]) -> (spikes, v_final)`` used in layer_serial mode (e.g. the
        Pallas kernel); defaults to the pure-jnp reference. The ``v0``
        positional is only passed when ``state`` is given, so legacy
        two-argument callables keep working for stateless calls.
      fuse_fc: layer_serial only -- run fc1/fc2 through the fused
        synapse+LIF Pallas kernel (one launch computes ``spikes @ W`` and
        the LIF update; the (T, B, N) current tensors never reach HBM).
        Bitwise-identical to the unfused path (pinned by tests at
        B in {1, 4, 8}).
      fc_lif_scan_fn: optional override for the fused fc scan,
        ``f(spikes_T_first, W, LIFParams[, v0]) -> (spikes, v_final)``;
        defaults to :func:`repro.kernels.ops.fc_lif_scan`.
      state: optional carried state from :func:`snn_init_state` (or a
        previous call's ``out["state"]``): per-layer (B, ...) membrane
        planes. The initial spike state is the one *implied* by the
        membrane (``s0 = v0 >= v_th``), matching the kernel/oracle
        window-chaining contract: running T steps in W chained chunks is
        bitwise identical to one uninterrupted T-step run, in every mode.
        ``None`` starts from rest (zero membrane).

    Returns:
      dict with ``spikes`` -- each LIF layer's time-major (T, B, ...)
      spike train, for layer-by-layer comparison against a reference
      (unused outputs cost nothing under jit) -- ``out_spikes``
      (B, T, num_classes), ``out_membrane``
      (B, T, num_classes) in time_serial mode, per-layer mean firing
      rates, ``firing_rates_per_stream`` -- per-layer (B,) rates so
      the batched closed loop can drive the energy model per stream --
      and ``state``: the per-layer (B, ...) final membranes, feedable
      back as ``state`` to continue the stream.
    """
    if fuse_fc and mode != "layer_serial":
        raise ValueError(f"fuse_fc requires mode='layer_serial', got {mode!r}")
    b, t = vox.shape[0], vox.shape[1]
    x = jnp.transpose(vox, (1, 0, 3, 4, 2))  # (T, B, H, W, C)
    i1, i2, i3, i4 = _currents_fn(params, cfg)
    lif = cfg.lif

    # Mean firing rate per stream: reduce every axis except batch. Streams
    # are independent rows, so these values do not depend on batch size --
    # the property the batched-vs-looped parity tests pin down.
    def rate_b(s: jnp.ndarray, batch_axis: int) -> jnp.ndarray:
        axes = tuple(a for a in range(s.ndim) if a != batch_axis)
        return s.mean(axis=axes)

    if mode == "time_serial":
        if state is None:
            h0, w0 = cfg.post_pool0
            zeros = lambda shape: jnp.zeros((b, *shape), x.dtype)
            carry = {
                "v1": zeros((h0, w0, cfg.conv1_features)),
                "s1": zeros((h0, w0, cfg.conv1_features)),
                "v2": zeros((h0 // 2, w0 // 2, cfg.conv2_features)),
                "s2": zeros((h0 // 2, w0 // 2, cfg.conv2_features)),
                "v3": zeros((cfg.hidden,)), "s3": zeros((cfg.hidden,)),
                "v4": zeros((cfg.num_classes,)),
                "s4": zeros((cfg.num_classes,)),
            }
        else:
            # Window-chaining contract: the carried membrane implies the
            # spike state (s0 = v0 >= v_th), exactly as in
            # ``lif_scan_reference`` and the Pallas kernels.
            def v_s(v):
                v = v.astype(jnp.float32)
                s = spike_surrogate(v, jnp.float32(lif.v_th),
                                    lif.surrogate_width).astype(x.dtype)
                return v, s

            carry = {}
            for i, name in enumerate(SNN_STATE_LAYERS, start=1):
                carry[f"v{i}"], carry[f"s{i}"] = v_s(state[name])

        def step(c, x_t):
            v1, s1 = lif_step(c["v1"], c["s1"], i1(x_t), lif)
            v2, s2 = lif_step(c["v2"], c["s2"], i2(s1), lif)
            v3, s3 = lif_step(c["v3"], c["s3"], i3(s2), lif)
            v4, s4 = lif_step(c["v4"], c["s4"], i4(s3), lif)
            new = {"v1": v1, "s1": s1, "v2": v2, "s2": s2,
                   "v3": v3, "s3": s3, "v4": v4, "s4": s4}
            rates = (rate_b(s1, 0), rate_b(s2, 0),
                     rate_b(s3, 0), rate_b(s4, 0))        # each (B,)
            return new, ((s1, s2, s3, s4), v4, rates)

        fin, (trains, out_v, rates) = jax.lax.scan(step, carry, x)
        s1, s2, s3, s4 = trains                          # each (T, B, ...)
        out_spikes = jnp.transpose(s4, (1, 0, 2))        # (B, T, classes)
        out_membrane = jnp.transpose(out_v, (1, 0, 2))
        r1, r2, r3, r4 = (r.mean(axis=0) for r in rates)  # (T, B) -> (B,)
        state_out = {name: fin[f"v{i}"]
                     for i, name in enumerate(SNN_STATE_LAYERS, start=1)}
    elif mode == "layer_serial":
        scan = lif_scan_fn or lif_scan_reference
        # v0 is only passed when carried state is given, so legacy
        # two-argument lif_scan_fn callables stay valid stateless.
        run_scan = (lambda cur, v0: scan(cur, lif) if v0 is None
                    else scan(cur, lif, v0))
        v0 = lambda name: None if state is None else state[name]
        # Layer 2: conv1 + LIF over the full train. Each layer is a
        # named scope, so a profile of the compiled step names it.
        with jax.named_scope("conv1"):
            c1 = jax.vmap(i1)(x)              # (T, B, h0, w0, f1)
            s1, vf1 = run_scan(c1, v0("conv1"))
        with jax.named_scope("conv2"):
            c2 = jax.vmap(i2)(s1)
            s2, vf2 = run_scan(c2, v0("conv2"))
        if fuse_fc:
            fc_scan = fc_lif_scan_fn
            if fc_scan is None:
                # Lazy import: core -> kernels only on the fused path.
                from repro.kernels.ops import fc_lif_scan as fc_scan
            run_fc = (lambda s, w, v: fc_scan(s, w, lif) if v is None
                      else fc_scan(s, w, lif, v))
            # Pool+flatten stays outside the kernel (cheap, bandwidth-
            # bound); the matmul+LIF of fc1/fc2 fuse into one launch
            # each, so their (T, B, N) current tensors never reach HBM.
            def pool_flat(s_t):
                pooled = _avg_pool(s_t, 2)
                return pooled.reshape(pooled.shape[0], -1)

            with jax.named_scope("fc1"):
                z = jax.vmap(pool_flat)(s2)   # (T, B, flat_dim)
                s3, vf3 = run_fc(z, params["fc1"]["w"], v0("fc1"))
            with jax.named_scope("fc2"):
                s4, vf4 = run_fc(s3, params["fc2"]["w"], v0("fc2"))
        else:
            with jax.named_scope("fc1"):
                c3 = jax.vmap(i3)(s2)
                s3, vf3 = run_scan(c3, v0("fc1"))
            with jax.named_scope("fc2"):
                c4 = jax.vmap(i4)(s3)
                s4, vf4 = run_scan(c4, v0("fc2"))
        out_spikes = jnp.transpose(s4, (1, 0, 2))
        out_membrane = jnp.zeros_like(out_spikes)  # not tracked in this mode
        # Layer outputs are (T, B, ...): batch axis 1.
        r1, r2, r3, r4 = (rate_b(s, 1) for s in (s1, s2, s3, s4))
        state_out = {"conv1": vf1, "conv2": vf2, "fc1": vf3, "fc2": vf4}
    else:
        raise ValueError(f"unknown mode: {mode}")

    per_stream = {"conv1": r1, "conv2": r2, "fc1": r3, "fc2": r4}
    return {
        "spikes": {"conv1": s1, "conv2": s2, "fc1": s3, "fc2": s4},
        "out_spikes": out_spikes,
        "out_membrane": out_membrane,
        "firing_rates": {k: v.mean() for k, v in per_stream.items()},
        "firing_rates_per_stream": per_stream,
        "state": state_out,
    }


def snn_logits(outputs: Dict[str, jnp.ndarray], cfg: SNNConfig) -> jnp.ndarray:
    """Readout: spike-count (hardware-faithful) or mean-membrane logits."""
    if cfg.readout == "spike_count":
        return outputs["out_spikes"].mean(axis=1)
    return outputs["out_membrane"].mean(axis=1)


def snn_loss(
    params: Params,
    vox: jnp.ndarray,
    labels: jnp.ndarray,
    cfg: SNNConfig,
    *,
    mode: str = "time_serial",
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """STBP cross-entropy loss on readout logits. Returns (loss, aux)."""
    out = snn_apply(params, vox, cfg, mode=mode)
    # Spike-count readout gives logits in [0,1]; scale for usable softmax
    # temperature (equivalently a fixed readout gain, absorbed by training).
    logits = snn_logits(out, cfg) * 10.0
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    acc = (jnp.argmax(logits, -1) == labels).mean()
    return loss, {"accuracy": acc, "firing_rates": out["firing_rates"],
                  "logits": logits}
