"""Fused synapse+LIF Pallas kernel for the fully-connected SNN layers.

The plain ``layer_serial`` hot path materializes every fc layer's full
(T, B, N) synaptic-current tensor to HBM (``spikes @ W`` under vmap) and
then re-reads it inside the fused LIF scan. SNE never does that: spikes
stream *through* the engine while weights and membrane state stay inside
it. This kernel is the TPU mapping of that dataflow for the fc1/fc2
layers (2048 -> 512 -> 11, the FLOPs-dominant stages):

  * one launch computes ``spikes[t] @ W`` on the MXU *and* the LIF update
    on the VPU, timestep block by timestep block;
  * the (K, block_n) weight panel and the (B, block_n) membrane plane are
    VMEM-resident across the whole temporal scan (weight index map is
    constant in the sequential T-chunk grid axis, membrane lives in VMEM
    scratch);
  * synaptic currents are consumed the moment they are produced -- they
    never touch HBM. HBM traffic drops from
    ``T*B*(K + 3N)`` words (currents written + read, spikes out) to
    ``T*B*(K + N)`` (spikes in / spikes out) per layer.

Grid: (N tiles, T chunks). The N axis is parallel; the T-chunk axis is
sequential ("arbitrary") and carries the membrane plane in scratch --
SNE's time-domain-multiplexed pass structure with an output-neuron panel
as the capacity unit.

Numerics are bitwise identical to the unfused path (XLA computes each
output element of a f32 matmul as an independent K-dot, so chunking T or
padding N with zero columns changes nothing; the LIF update is the exact
expression of ``lif_scan_reference``) -- pinned by tests at B in
{1, 4, 8}.

Recurrence (reset-to-zero LIF, single carried state):
    I[t] = S_in[t] @ W
    V[t] = alpha * V[t-1] * (V[t-1] < v_th) + I[t]
    S[t] = V[t] >= v_th
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lif import LIFParams
from repro.kernels.backend import use_interpret

__all__ = ["fc_lif_scan_pallas", "fc_lif_scan_pallas_batched",
           "choose_fc_blocks"]

LANES = 128
# Weights + a T-block of spikes in/out + currents (value and scratch) +
# state must fit; the full-model fc1 panel (2048 x 512 f32 = 4 MiB) plus
# a 16-step block at B=8 uses ~5.8 MiB of the 8 MiB default.
_DEF_VMEM_BUDGET = 8 * 1024 * 1024


def choose_fc_blocks(
    t: int, b: int, k: int, n: int, dtype,
    vmem_budget: int = _DEF_VMEM_BUDGET,
) -> Tuple[int, int]:
    """Pick (block_t, block_n) so the fused fc+LIF working set fits VMEM.

    Per (T-chunk, N-tile) step the kernel holds: the (K, block_n) weight
    panel, two f32 state planes (membrane scratch + v0), block_t rows of
    input spikes (B, K), and block_t rows of output spikes, of the f32
    currents the matmul produces, and of the f32 current scratch the time
    loop reads them from (B, block_n). Shrinks block_n (lane-multiple)
    before block_t; raises when even a (1, LANES) tile exceeds the
    budget -- never silently overcommits.
    """
    esize = jnp.dtype(dtype).itemsize
    n_padded = n + ((-n) % LANES)
    block_n = min(n_padded, 4 * LANES)
    while True:
        w_bytes = 4 * k * block_n
        state_bytes = 2 * 4 * b * block_n
        per_t = b * (k * esize + block_n * (esize + 4 + 4))
        avail = vmem_budget - w_bytes - state_bytes
        if avail >= per_t:
            return int(min(max(avail // per_t, 1), t)), block_n
        if block_n > LANES:
            block_n = max((block_n // 2) // LANES * LANES, LANES)
            continue
        need = w_bytes + state_bytes + per_t
        raise ValueError(
            f"vmem_budget={vmem_budget} too small for fc_lif_scan: one "
            f"(block_t=1, block_n={LANES}) step over K={k}, B={b} needs "
            f"{need} bytes")


def _kernel(spk_ref, w_ref, v0_ref, out_ref, vfin_ref, v_scr, cur_scr,
            *, alpha: float, v_th: float, t_total: int, block_t: int):
    tc = pl.program_id(1)
    n_tc = pl.num_programs(1)

    @pl.when(tc == 0)
    def _init():
        v_scr[...] = v0_ref[...].astype(jnp.float32)

    # Synapse stage: all block_t timesteps' currents in one MXU call.
    # (block_t*B, K) @ (K, block_n) is bitwise the same per output element
    # as the unfused vmap-over-T matmul (independent K-dots). The result
    # is staged in VMEM scratch so the time loop reads it through a ref:
    # Mosaic cannot index an in-register value with the loop counter.
    bt, b, k = spk_ref.shape
    cur_scr[...] = jnp.dot(
        spk_ref[...].reshape(bt * b, k).astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,   # see core.snn.HIGHEST
        preferred_element_type=jnp.float32,
    ).reshape(bt, b, -1)

    def step(i, v):
        # Global timestep; guards the T padding tail (padded steps must
        # not advance the dynamics).
        in_range = tc * block_t + i < t_total
        cur = cur_scr[i]
        live = (v < v_th).astype(jnp.float32)       # reset-to-zero mask
        v_new = alpha * v * live + cur
        s = (v_new >= v_th).astype(out_ref.dtype)
        out_ref[i, :, :] = jnp.where(in_range, s, jnp.zeros_like(s))
        return jnp.where(in_range, v_new, v)

    v = jax.lax.fori_loop(0, block_t, step, v_scr[...])
    v_scr[...] = v

    @pl.when(tc == n_tc - 1)
    def _fin():
        vfin_ref[...] = v.astype(vfin_ref.dtype)


def fc_lif_scan_pallas(
    spikes: jnp.ndarray,
    w: jnp.ndarray,
    p: LIFParams,
    v0: jnp.ndarray | None = None,
    *,
    block_t: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
    vmem_budget: int = _DEF_VMEM_BUDGET,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused ``spikes @ w`` + LIF scan. Returns (out_spikes, v_final).

    Args:
      spikes: (T, B, K) -- or (T, K), treated as B=1 -- input spike train.
      w: (K, N) synaptic weights.
      p: LIF constants.
      v0: optional initial membrane, (B, N) (or (N,) for 2-D spikes).

    Forward-only (no AD rules); use ``repro.kernels.ops.fc_lif_scan`` for
    the differentiable (STBP surrogate) wrapper.
    """
    interpret = use_interpret(interpret)
    squeeze = spikes.ndim == 2
    if squeeze:
        spikes = spikes[:, None, :]
        if v0 is not None:
            v0 = v0[None]
    if spikes.ndim != 3:
        raise ValueError(f"need (T, B, K) spikes, got {spikes.shape}")
    t, b, k = spikes.shape
    kw, n = w.shape
    if kw != k:
        raise ValueError(f"spikes K={k} != weights K={kw}")
    if v0 is None:
        v0 = jnp.zeros((b, n), spikes.dtype)

    bt, bn = choose_fc_blocks(t, b, k, n, spikes.dtype, vmem_budget)
    if block_t is not None:
        bt = block_t
    if block_n is not None:
        bn = block_n
    if bn % LANES:
        raise ValueError(f"block_n={bn} must be a multiple of {LANES}")

    # Pad N to a block multiple with zero weight columns (each output
    # column is independent, so padding never changes live columns) and
    # T to a block multiple (tail masked inside the kernel). K is the
    # contraction axis and is deliberately NOT padded.
    n_pad = (-n) % bn
    t_pad = (-t) % bt
    w_p = jnp.pad(w, ((0, 0), (0, n_pad))) if n_pad else w
    v0_p = jnp.pad(v0, ((0, 0), (0, n_pad))) if n_pad else v0
    spk = jnp.pad(spikes, ((0, t_pad), (0, 0), (0, 0))) if t_pad else spikes
    tt, nn = t + t_pad, n + n_pad

    grid = (nn // bn, tt // bt)
    kernel = functools.partial(
        _kernel, alpha=float(p.alpha), v_th=float(p.v_th),
        t_total=t, block_t=bt,
    )
    out, v_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # Input spikes revisit the same (block_t, B, K) slab for every
            # N tile; the weight panel's index map is constant along the
            # sequential T axis, so it stays VMEM-resident for the scan.
            pl.BlockSpec((bt, b, k), lambda ni, ti: (ti, 0, 0)),
            pl.BlockSpec((k, bn), lambda ni, ti: (0, ni)),
            pl.BlockSpec((b, bn), lambda ni, ti: (0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((bt, b, bn), lambda ni, ti: (ti, 0, ni)),
            pl.BlockSpec((b, bn), lambda ni, ti: (0, ni)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tt, b, nn), spikes.dtype),
            jax.ShapeDtypeStruct((b, nn), spikes.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((b, bn), jnp.float32),
                        pltpu.VMEM((bt, b, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(spk, w_p, v0_p)

    out = out[:t, :, :n]
    v_fin = v_fin[:, :n]
    if squeeze:
        out, v_fin = out[:, 0, :], v_fin[0]
    return out, v_fin


def fc_lif_scan_pallas_batched(
    spikes: jnp.ndarray,
    w: jnp.ndarray,
    p: LIFParams,
    v0: jnp.ndarray | None = None,
    **kw,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stream-major entry: (B, T, K) spikes -> ((B, T, N), (B, N)).

    The kernel itself is batched (its sublane axis is B); this wrapper
    only transposes to the kernel's time-major layout and threads the
    per-stream ``v0`` -- the shape the stateful-streaming API hands over
    when carrying fc membrane across a stream's windows.
    """
    if spikes.ndim != 3:
        raise ValueError(f"need (B, T, K) spikes, got {spikes.shape}")
    out, v_fin = fc_lif_scan_pallas(
        jnp.transpose(spikes, (1, 0, 2)), w, p, v0, **kw)
    return jnp.transpose(out, (1, 0, 2)), v_fin
