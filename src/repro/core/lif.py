"""Leaky-integrate-and-fire neuron dynamics with surrogate gradients.

This models the neuron implemented in silicon by SNE (the Kraken SoC's
sparse neural engine). Per the paper (Sec. III), training uses
spatio-temporal backpropagation (STBP, Wu et al. 2018) with the neuron
dynamics "accurately modeled ... to closely reflect the hardware
implementation", i.e. a discrete-time LIF with multiplicative leak and
reset-to-zero:

    V[t] = alpha * V[t-1] * (1 - S[t-1]) + I[t]
    S[t] = Heaviside(V[t] - v_th)

The Heaviside gets a rectangular surrogate derivative (STBP eq. 24):
    dS/dV ~= 1/a * 1{|V - v_th| < a/2}

Two execution paths exist:
  * ``lif_scan_reference`` -- pure jnp ``lax.scan`` (the oracle; also the
    bwd path used by the custom VJP).
  * ``repro.kernels.lif_scan`` -- the fused Pallas kernel (SNE analogue;
    membrane state resident in VMEM for the whole temporal scan).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "LIFParams",
    "LIFLayer",
    "spike_surrogate",
    "lif_step",
    "lif_scan_reference",
]


@dataclasses.dataclass(frozen=True)
class LIFParams:
    """LIF neuron constants (hardware-calibrated in SNE's case)."""

    alpha: float = 0.875     # membrane leak per step (SNE uses 1 - 2^-k leaks)
    v_th: float = 0.5        # firing threshold
    surrogate_width: float = 2.0  # 'a' in the STBP rectangular surrogate


class LIFLayer(NamedTuple):
    """One LIF layer of an event network, as the serving engine accounts
    it: its output volume ``shape`` (H, W, C) for the tiling planner,
    ``fanout`` -- the synapses each of its input spikes drives -- and
    ``source``, the layer whose spikes feed it (``None``: the events).
    Each network config's ``lif_layers()`` lists them in execution
    order; they also name the carried-state planes and the per-stream
    firing rates."""

    name: str
    shape: Tuple[int, int, int]
    fanout: float
    source: Optional[str]


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def spike_surrogate(v: jnp.ndarray, v_th: jnp.ndarray, width: float = 1.0):
    """Heaviside spike with rectangular surrogate gradient (STBP)."""
    return (v >= v_th).astype(v.dtype)


def _spike_fwd(v, v_th, width):
    return spike_surrogate(v, v_th, width), (v, v_th)


def _spike_bwd(width, res, g):
    v, v_th = res
    inside = (jnp.abs(v - v_th) < (width / 2.0)).astype(v.dtype)
    grad_v = g * inside / width
    return (grad_v, -jnp.sum(grad_v).astype(v_th.dtype) * 0)  # v_th: no grad


spike_surrogate.defvjp(_spike_fwd, _spike_bwd)


def lif_step(
    v: jnp.ndarray,
    s_prev: jnp.ndarray,
    current: jnp.ndarray,
    p: LIFParams,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One LIF timestep. Returns (new membrane f32, new spikes).

    Membrane is carried in f32 (the kernel/oracle numerical contract --
    SNE keeps wide fixed-point state in-engine).
    """
    v_new = (p.alpha * v.astype(jnp.float32) * (1.0 - s_prev.astype(jnp.float32))
             + current.astype(jnp.float32))
    s_new = spike_surrogate(v_new, jnp.float32(p.v_th),
                            p.surrogate_width).astype(current.dtype)
    return v_new, s_new


def lif_scan_reference(
    currents: jnp.ndarray,
    p: LIFParams,
    v0: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan LIF dynamics over time (pure jnp oracle).

    Args:
      currents: input currents, shape (T, ...) -- leading axis is time.
      p: neuron constants.
      v0: optional initial membrane, shape currents.shape[1:].

    Returns:
      (spikes, v_final): spikes has the same shape as ``currents``;
      v_final the final membrane state.

    Stateful-streaming contract: the initial spike state is the one
    *implied* by the membrane, ``s0 = (v0 >= v_th)`` -- ``v_final`` is
    returned pre-reset, so a caller chaining windows via
    ``v0=v_final`` gets exactly the uninterrupted scan
    (``scan(cur[:k]) ++ scan(cur[k:], v0=v_fin)`` == ``scan(cur)``,
    bitwise). This matches the Pallas kernel and ``lif_scan_ref``, whose
    reset masks are computed from the carried membrane directly.
    """
    if v0 is None:
        v0 = jnp.zeros(currents.shape[1:], jnp.float32)
        s0 = jnp.zeros(currents.shape[1:], currents.dtype)
    else:
        s0 = spike_surrogate(v0.astype(jnp.float32), jnp.float32(p.v_th),
                             p.surrogate_width).astype(currents.dtype)

    def step(carry, i_t):
        v, s = carry
        v, s = lif_step(v, s, i_t, p)
        return (v, s), s

    (v_final, _), spikes = jax.lax.scan(
        step, (v0.astype(jnp.float32), s0), currents)
    return spikes, v_final.astype(currents.dtype)
