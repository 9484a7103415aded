"""Fused RWKV-6 WKV recurrence Pallas kernel.

The same state-residency insight as ``lif_scan`` (SNE keeps neuron state
in-engine; DESIGN.md maps it to VMEM): the per-head (hd x hd) WKV state
stays in VMEM scratch across the whole sequence while r/k/v/decay stream
through, instead of being re-materialized to HBM every step (the naive
scan) or every chunk boundary (the chunked-parallel form).

    o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T,   w_t = exp(logw_t)

Layout: heads x batch flattened to BH; blocks of ``block_bh`` heads are
processed per grid row with all per-step tensors (bh, hd) and the state
(bh, hd, hd) resident in VMEM. hd = 64 fills half a lane row -- an
acknowledged sub-optimality (a 2-head lane-packing variant is the next
hillclimb step on real hardware).

Grid: (BH tiles, T chunks); T sequential ("arbitrary") with the state in
scratch, exactly the lif_scan pattern.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import use_interpret

__all__ = ["wkv6_scan_pallas"]

_DEF_VMEM_BUDGET = 8 * 1024 * 1024


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sfin_ref, s_scr,
            *, block_t: int, t_total: int):
    tc = pl.program_id(1)
    n_tc = pl.num_programs(1)

    @pl.when(tc == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[...].astype(jnp.float32)              # (bh, hd)

    def step(i, s):
        in_range = tc * block_t + i < t_total
        r = r_ref[i, :, :].astype(jnp.float32)      # (bh, hd)
        k = k_ref[i, :, :].astype(jnp.float32)
        v = v_ref[i, :, :].astype(jnp.float32)
        w = jnp.exp(lw_ref[i, :, :].astype(jnp.float32))
        kv = k[:, :, None] * v[:, None, :]          # (bh, hd, hd)
        o = jnp.sum((s + u[:, :, None] * kv) * r[:, :, None], axis=1)
        o_ref[i, :, :] = jnp.where(in_range, o, 0.0).astype(o_ref.dtype)
        s_new = w[:, :, None] * s + kv
        return jnp.where(in_range, s_new, s)

    s = jax.lax.fori_loop(0, block_t, step, s_scr[...])
    s_scr[...] = s

    @pl.when(tc == n_tc - 1)
    def _fin():
        sfin_ref[...] = s.astype(sfin_ref.dtype)


def wkv6_scan_pallas(
    r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, logw: jnp.ndarray,
    u: jnp.ndarray,
    *,
    block_bh: int = 8,
    block_t: int | None = None,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused WKV-6 scan. r/k/v/logw: (B, T, H, hd); u: (H, hd).

    Returns (o (B, T, H, hd), state (B, H, hd, hd) f32). Oracle:
    ``repro.kernels.ref.wkv6_ref`` (per head) / ``wkv6_chunked``.
    """
    interpret = use_interpret(interpret)
    b, t, h, hd = r.shape
    bh = b * h

    def to_bh(x):  # (B, T, H, hd) -> (BH, T, hd) -> (T, BH, hd)
        return x.transpose(0, 2, 1, 3).reshape(bh, t, hd).transpose(1, 0, 2)

    rr, kk, vv, lw = (to_bh(x) for x in (r, k, v, logw))
    ub = jnp.broadcast_to(u[None], (b, h, hd)).reshape(bh, hd)

    pad_bh = (-bh) % block_bh
    if pad_bh:
        rr, kk, vv, lw = (jnp.pad(x, ((0, 0), (0, pad_bh), (0, 0)))
                          for x in (rr, kk, vv, lw))
        ub = jnp.pad(ub, ((0, pad_bh), (0, 0)))
    bhp = bh + pad_bh

    if block_t is None:
        esize = jnp.dtype(r.dtype).itemsize
        per_t = 5 * esize * block_bh * hd
        state = 4 * block_bh * hd * hd
        block_t = int(min(t, max((_DEF_VMEM_BUDGET - state) // per_t, 8)))
    pad_t = (-t) % block_t
    if pad_t:
        rr, kk, vv, lw = (jnp.pad(x, ((0, pad_t), (0, 0), (0, 0)))
                          for x in (rr, kk, vv, lw))
    tt = t + pad_t

    grid = (bhp // block_bh, tt // block_t)
    kernel = functools.partial(_kernel, block_t=block_t, t_total=t)
    seq_spec = pl.BlockSpec((block_t, block_bh, hd),
                            lambda bi, ti: (ti, bi, 0))
    o, s_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((block_bh, hd), lambda bi, ti: (bi, 0))],
        out_specs=[seq_spec,
                   pl.BlockSpec((block_bh, hd, hd),
                                lambda bi, ti: (bi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((tt, bhp, hd), r.dtype),
                   jax.ShapeDtypeStruct((bhp, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_bh, hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rr, kk, vv, lw, ub)

    o = o[:t, :bh].transpose(1, 0, 2).reshape(b, h, t, hd)
    o = o.transpose(0, 2, 1, 3)
    s_fin = s_fin[:bh].reshape(b, h, hd, hd)
    return o, s_fin
