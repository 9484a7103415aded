"""Jit'd public wrappers around the Pallas kernels.

``lif_scan``       -- differentiable fused LIF scan (STBP surrogate VJP).
``fc_lif_scan``    -- differentiable fused synapse(matmul)+LIF scan for
                      the fully-connected layers (currents never hit HBM).
``ternary_matmul`` -- packed-ternary GEMM (serving path, fwd-only).
``pack_ternary_weights`` -- float weights -> (packed uint8, scale) in the
                            kernel's (K//4, N) layout.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.lif import LIFParams, lif_scan_reference
from repro.core.ternary import pack2bit, ternarize
from repro.kernels.fc_lif_scan import fc_lif_scan_pallas
from repro.kernels.lif_scan import lif_scan_pallas, lif_scan_pallas_batched
from repro.kernels.ternary_matmul import ternary_matmul_pallas

__all__ = ["lif_scan", "lif_scan_batched", "fc_lif_scan",
           "fc_lif_scan_batched", "ternary_matmul", "pack_ternary_weights"]


# ----------------------------------------------------------------------
# LIF scan: Pallas forward, STBP-surrogate backward (recompute-based, i.e.
# the backward re-runs the cheap reference scan under jax.vjp -- a remat
# policy, not an approximation; forward values are bit-identical).
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lif_scan_cv(currents, v0, p: LIFParams):
    return lif_scan_pallas(currents, p, v0)


def _lif_fwd(currents, v0, p):
    out = _lif_scan_cv(currents, v0, p)
    return out, (currents, v0)


def _lif_bwd(p, res, cotangents):
    currents, v0 = res
    _, vjp = jax.vjp(lambda c, v: lif_scan_reference(c, p, v), currents, v0)
    return vjp(cotangents)


_lif_scan_cv.defvjp(_lif_fwd, _lif_bwd)


def lif_scan(
    currents: jnp.ndarray,
    p: LIFParams = LIFParams(),
    v0: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused LIF scan over (T, ...) currents -> (spikes, v_final).

    Drop-in for :func:`repro.core.lif.lif_scan_reference` (same numerics,
    same STBP surrogate gradients), with the temporal scan fused into a
    single Pallas kernel (membrane state VMEM-resident; see
    ``kernels/lif_scan.py``).
    """
    if v0 is None:
        v0 = jnp.zeros(currents.shape[1:], currents.dtype)
    return _lif_scan_cv(currents, v0, p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lif_scan_batched_cv(currents, v0, p: LIFParams):
    return lif_scan_pallas_batched(currents, p, v0)


def _lif_b_fwd(currents, v0, p):
    return _lif_scan_batched_cv(currents, v0, p), (currents, v0)


def _lif_b_bwd(p, res, cotangents):
    currents, v0 = res
    ref = jax.vmap(lambda c, v: lif_scan_reference(c, p, v))
    _, vjp = jax.vjp(ref, currents, v0)
    return vjp(cotangents)


_lif_scan_batched_cv.defvjp(_lif_b_fwd, _lif_b_bwd)


def lif_scan_batched(
    currents: jnp.ndarray,
    p: LIFParams = LIFParams(),
    v0: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused LIF scan over a batch of streams: (B, T, ...) -> (spikes, v_final).

    One Pallas launch for all ``B`` streams (batch folded into the kernel's
    neuron-row grid axis; see ``kernels/lif_scan.py``), with the same STBP
    surrogate gradients as :func:`lif_scan` (backward recomputes via the
    vmapped reference scan).

    Note the closed-loop engine reaches the same fold implicitly:
    ``layer_serial`` feeds :func:`lif_scan` currents shaped (T, B, ...),
    whose feature flattening already packs B into the row axis. This
    explicit (B, T, ...) entry additionally pads each stream to whole
    lane-rows (no cross-stream lanes) and threads a per-stream ``v0`` --
    the API for carrying membrane state across a stream's windows
    (stateful streaming, a ROADMAP open item).
    """
    if v0 is None:
        v0 = jnp.zeros((currents.shape[0], *currents.shape[2:]),
                       currents.dtype)
    return _lif_scan_batched_cv(currents, v0, p)


# ----------------------------------------------------------------------
# Fused synapse+LIF scan for the fully-connected layers: the matmul and
# the LIF update share one Pallas launch (currents never touch HBM).
# Backward recomputes through the matmul + reference scan (same remat
# policy as lif_scan; forward values are bit-identical to unfused).
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fc_lif_scan_cv(spikes, w, v0, bias, p: LIFParams):
    return fc_lif_scan_pallas(spikes, w, p, v0, bias=bias)


def _fc_fwd(spikes, w, v0, bias, p):
    return _fc_lif_scan_cv(spikes, w, v0, bias, p), (spikes, w, v0, bias)


def _fc_bwd(p, res, cotangents):
    spikes, w, v0, bias = res

    def ref(s, w_, v, b):
        cur = jnp.matmul(s, w_)
        return lif_scan_reference(cur if b is None else cur + b, p, v)

    _, vjp = jax.vjp(ref, spikes, w, v0, bias)
    return vjp(cotangents)


_fc_lif_scan_cv.defvjp(_fc_fwd, _fc_bwd)


def fc_lif_scan(
    spikes: jnp.ndarray,
    w: jnp.ndarray,
    p: LIFParams = LIFParams(),
    v0: jnp.ndarray | None = None,
    bias: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused ``spikes @ w (+ bias)`` + LIF scan -> (out_spikes, v_final).

    Drop-in for ``lif_scan_reference(spikes @ w + bias, p, v0)``
    (bitwise-equal forward, same STBP surrogate gradients) with the
    synaptic matmul and the temporal scan fused into one Pallas launch:
    weights and membrane stay VMEM-resident, the (T, B, N) current tensor
    never exists in HBM (see ``kernels/fc_lif_scan.py``).

    ``spikes``: (T, B, K) or (T, K); ``w``: (K, N); ``v0``: (B, N)/(N,);
    ``bias``: (N,) or None.
    """
    if v0 is None:
        shape = ((spikes.shape[1], w.shape[1]) if spikes.ndim == 3
                 else (w.shape[1],))
        v0 = jnp.zeros(shape, spikes.dtype)
    return _fc_lif_scan_cv(spikes, w, v0, bias, p)


def fc_lif_scan_batched(
    spikes: jnp.ndarray,
    w: jnp.ndarray,
    p: LIFParams = LIFParams(),
    v0: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stream-major fused fc+LIF: (B, T, K) -> ((B, T, N), (B, N)).

    The kernel is natively batched (B is its sublane axis); this wrapper
    transposes to time-major and threads the per-stream ``v0`` -- the
    entry point for carrying fc membrane state across a stream's windows.
    Differentiable via the same custom VJP as :func:`fc_lif_scan`.
    """
    if spikes.ndim != 3:
        raise ValueError(f"need (B, T, K) spikes, got {spikes.shape}")
    out, v_fin = fc_lif_scan(jnp.transpose(spikes, (1, 0, 2)), w, p, v0)
    return jnp.transpose(out, (1, 0, 2)), v_fin


# ----------------------------------------------------------------------
# Ternary GEMM (serving path).
# ----------------------------------------------------------------------

def pack_ternary_weights(
    w: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize (K, N) float weights to the kernel's packed layout.

    Returns (w_packed (K//4, N) uint8, scale (N,) f32). Per-output-channel
    TWN quantization (axis=-1 of the (K, N) matrix = output channel N).
    """
    k, n = w.shape
    if k % 4:
        raise ValueError(f"K={k} must be a multiple of 4 for 2-bit packing")
    q, scale = ternarize(w, axis=-1)          # q int8 (K, N); scale (1, N)
    packed = pack2bit(q.T).T                  # pack along K -> (K//4, N)
    return packed, scale.reshape(n).astype(jnp.float32)


@jax.jit
def ternary_matmul(
    x: jnp.ndarray,
    w_packed: jnp.ndarray,
    scale: jnp.ndarray,
) -> jnp.ndarray:
    """x (M, K) @ ternary (K, N) with in-kernel dequant; f32 accumulation."""
    return ternary_matmul_pallas(x, w_packed, scale)
