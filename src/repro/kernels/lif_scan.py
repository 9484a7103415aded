"""Fused LIF temporal-scan Pallas kernel -- the SNE analogue on TPU.

SNE (Kraken's sparse neural engine) keeps neuron membrane state *inside the
engine* while a spike train streams through; networks bigger than the
engine's neuron capacity are executed in capacity-sized tiles,
time-domain-multiplexed (paper Sec. III). The TPU mapping of that insight
(DESIGN.md): membrane state stays resident in VMEM scratch for the entire
temporal scan while input currents stream HBM->VMEM tile by tile. A naive
jnp ``lax.scan`` materializes V to HBM every step (2x state traffic per
step); the fused kernel touches HBM only for currents-in / spikes-out.

Layout: currents are processed as (T, R, 128) -- neurons split into
R = N/128 lane-rows, so each timestep's update is a full-width (R, 128)
VPU operation (sublane-dim >= 8 keeps the VPU busy; a flat (N,) row per
step would waste 7/8 sublanes).

Grid: (R tiles, T chunks); the T-chunk axis is sequential ("arbitrary")
and carries V in VMEM scratch across chunks -- exactly SNE's
time-multiplexed pass structure with the neuron tile as the capacity unit
(see ``repro.core.tiling``).

Recurrence (reset-to-zero LIF, single carried state):
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

__all__ = ["lif_scan_pallas", "lif_scan_pallas_batched", "choose_blocks",
           "LANES"]

LANES = 128
_DEF_VMEM_BUDGET = 4 * 1024 * 1024  # conservative per-call VMEM budget


def choose_blocks(
    t: int, r: int, dtype, vmem_budget: int = _DEF_VMEM_BUDGET
) -> Tuple[int, int]:
    """Pick (block_t, block_r) so currents+spikes+state tiles fit VMEM.

    This is the SNE capacity computation with VMEM bytes as the capacity
    (cf. ``repro.core.tiling.plan_layer_tiles(capacity_kind='vmem_bytes')``):
    per neuron-row tile we hold block_t rows of currents and spikes plus
    three f32 state planes. The preferred block_t floor of 8 (sublane
    efficiency) is honoured only while it fits: with a tiny budget
    block_t is clamped down to what the budget allows (>= 1), and a
    budget too small for even a (block_t=1, block_r=8) tile raises
    rather than silently overcommitting VMEM.
    """
    esize = jnp.dtype(dtype).itemsize
    block_r = min(r, 64)  # 64*128 f32 state = 32 KiB; >=8 sublanes
    while True:
        state_bytes = 3 * 4 * block_r * LANES
        per_t = 2 * esize * block_r * LANES
        fit_t = (vmem_budget - state_bytes) // per_t  # may be <= 0
        block_t = int(min(max(fit_t, 8), t))
        if state_bytes + block_t * per_t <= vmem_budget:
            return block_t, block_r
        if block_r > 8:
            block_r //= 2
            continue
        # Smallest row tile: clamp block_t below the sublane floor
        # instead of exceeding the budget.
        if fit_t >= 1:
            return int(min(fit_t, t)), block_r
        raise ValueError(
            f"vmem_budget={vmem_budget} too small for the LIF scan: one "
            f"(block_t=1, block_r=8) tile needs "
            f"{state_bytes + per_t} bytes "
            f"({state_bytes} state + {per_t} per timestep)")


def _kernel(cur_ref, v0_ref, spk_ref, vfin_ref, v_scr,
            *, alpha: float, v_th: float, t_total: int, block_t: int):
    tc = pl.program_id(1)
    n_tc = pl.num_programs(1)

    @pl.when(tc == 0)
    def _init():
        v_scr[...] = v0_ref[...].astype(jnp.float32)

    def step(i, v):
        # Global timestep; guards the T padding tail (padded steps must not
        # advance the dynamics, or v_final would decay past the true T).
        in_range = tc * block_t + i < t_total
        cur = cur_ref[i, :, :].astype(jnp.float32)
        live = (v < v_th).astype(jnp.float32)       # reset-to-zero mask
        v_new = alpha * v * live + cur
        s = (v_new >= v_th).astype(spk_ref.dtype)
        spk_ref[i, :, :] = jnp.where(in_range, s, jnp.zeros_like(s))
        return jnp.where(in_range, v_new, v)

    v = jax.lax.fori_loop(0, block_t, step, v_scr[...])
    v_scr[...] = v

    @pl.when(tc == n_tc - 1)
    def _fin():
        vfin_ref[...] = v.astype(vfin_ref.dtype)


def lif_scan_pallas(
    currents: jnp.ndarray,
    p: LIFParams,
    v0: jnp.ndarray | None = None,
    *,
    block_t: int | None = None,
    block_r: int | None = None,
    interpret: bool | None = None,
    vmem_budget: int = _DEF_VMEM_BUDGET,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused LIF scan over (T, ...) currents. Returns (spikes, v_final).

    Forward-only (no AD rules); use ``repro.kernels.ops.lif_scan`` for the
    differentiable (STBP surrogate) wrapper.
    """
    interpret = use_interpret(interpret)
    orig_shape = currents.shape
    t = orig_shape[0]
    n = 1
    for d in orig_shape[1:]:
        n *= d
    if v0 is None:
        v0 = jnp.zeros(orig_shape[1:], currents.dtype)

    cur = currents.reshape(t, n)
    v0f = v0.reshape(n)
    # Pad neurons to a whole number of 128-lane rows.
    n_pad = (-n) % LANES
    if n_pad:
        cur = jnp.pad(cur, ((0, 0), (0, n_pad)))
        v0f = jnp.pad(v0f, (0, n_pad))
    r = (n + n_pad) // LANES
    cur = cur.reshape(t, r, LANES)
    v0r = v0f.reshape(r, LANES)

    bt, br = choose_blocks(t, r, currents.dtype, vmem_budget)
    if block_t is not None:
        bt = block_t
    if block_r is not None:
        br = block_r
    # Pad T and R to block multiples (T tail masked inside the kernel).
    t_pad, r_pad = (-t) % bt, (-r) % br
    if t_pad or r_pad:
        cur = jnp.pad(cur, ((0, t_pad), (0, r_pad), (0, 0)))
        v0r = jnp.pad(v0r, ((0, r_pad), (0, 0)))
    tt, rr = t + t_pad, r + r_pad

    grid = (rr // br, tt // bt)
    kernel = functools.partial(
        _kernel, alpha=float(p.alpha), v_th=float(p.v_th),
        t_total=t, block_t=bt,
    )
    spikes, v_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, br, LANES), lambda ri, ti: (ti, ri, 0)),
            pl.BlockSpec((br, LANES), lambda ri, ti: (ri, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, br, LANES), lambda ri, ti: (ti, ri, 0)),
            pl.BlockSpec((br, LANES), lambda ri, ti: (ri, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tt, rr, LANES), currents.dtype),
            jax.ShapeDtypeStruct((rr, LANES), currents.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((br, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="lif_scan",
    )(cur, v0r)

    spikes = spikes[:t].reshape(t, (n + n_pad))[:, :n].reshape(orig_shape)
    v_fin = v_fin.reshape(rr * LANES)[:n].reshape(orig_shape[1:])
    return spikes, v_fin


def lif_scan_pallas_batched(
    currents: jnp.ndarray,
    p: LIFParams,
    v0: jnp.ndarray | None = None,
    **kw,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused LIF scan over a batch of streams: (B, T, ...) -> (spikes, v_final).

    One Pallas launch scans all ``B`` streams: each stream's neurons are
    padded to whole 128-lane rows and the per-stream rows are stacked along
    the neuron-row axis, so the kernel's parallel grid axis enumerates
    ``B * R`` rows and every stream's membrane state is VMEM-resident for
    the whole temporal scan -- SNE's time-multiplexed execution, stream-
    multiplexed too. LIF dynamics are elementwise per neuron, so results
    are bitwise identical to ``B`` independent :func:`lif_scan_pallas`
    calls.

    Returns ``spikes`` of shape (B, T, ...) and ``v_final`` of (B, ...).
    """
    if currents.ndim < 2:
        raise ValueError(f"need (B, T, ...) currents, got {currents.shape}")
    b, t = currents.shape[0], currents.shape[1]
    feat = currents.shape[2:]
    n = 1
    for d in feat:
        n *= d
    if v0 is None:
        v0 = jnp.zeros((b, *feat), currents.dtype)

    cur = currents.reshape(b, t, n)
    v0f = v0.reshape(b, n)
    # Per-stream lane padding: each stream occupies whole rows, keeping its
    # rows contiguous on the row axis (cheap unfold, no cross-stream lanes).
    n_pad = (-n) % LANES
    if n_pad:
        cur = jnp.pad(cur, ((0, 0), (0, 0), (0, n_pad)))
        v0f = jnp.pad(v0f, ((0, 0), (0, n_pad)))
    r_s = (n + n_pad) // LANES        # rows per stream
    cur_rows = jnp.transpose(cur.reshape(b, t, r_s, LANES), (1, 0, 2, 3))
    cur_rows = cur_rows.reshape(t, b * r_s, LANES)
    v0_rows = v0f.reshape(b * r_s, LANES)

    spikes, v_fin = lif_scan_pallas(cur_rows, p, v0_rows, **kw)

    spikes = spikes.reshape(t, b, r_s * LANES)[:, :, :n]
    spikes = jnp.transpose(spikes, (1, 0, 2)).reshape(b, t, *feat)
    v_fin = v_fin.reshape(b, r_s * LANES)[:, :n].reshape(b, *feat)
    return spikes, v_fin
