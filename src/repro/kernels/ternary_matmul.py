"""Packed-ternary matmul Pallas kernel -- the CUTIE analogue on TPU.

CUTIE (Kraken's ternary accelerator) executes {-1,0,+1}-weight networks
with silicon-unrolled ternary MACs. On TPU the dense bf16 MXU is fixed, so
the transferable win is *weight bandwidth* (DESIGN.md): weights live in HBM
packed 4-per-byte (2 bit each) and are unpacked + dequantized in VMEM right
before hitting the MXU. For memory-bound shapes (LM decode GEMVs) this cuts
weight traffic 8x vs bf16 -- the same reason CUTIE wins on energy.

Layout:
  x        (M, K)      activations, f32/bf16
  w_packed (K//4, N)   uint8; byte row j holds ternary weights for K
                       indices 4j..4j+3 (little-endian 2-bit fields)
  scale    (1, N)      per-output-channel dequant scale
  out      (M, N)      x.dtype, f32 accumulation

Grid (M tiles, N tiles, K tiles); K is the sequential accumulation axis
with an f32 VMEM scratch accumulator, epilogue applies the channel scale.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import use_interpret

__all__ = ["ternary_matmul_pallas", "choose_blocks_tmm"]

_DEF_VMEM_BUDGET = 8 * 1024 * 1024


def choose_blocks_tmm(
    m: int, n: int, k: int, dtype, vmem_budget: int = _DEF_VMEM_BUDGET
) -> Tuple[int, int, int]:
    """MXU-aligned (block_m, block_n, block_k) within the VMEM budget."""
    esize = jnp.dtype(dtype).itemsize
    bm = min(max(8, m), 256)
    bn = min(max(128, n), 512)
    bk = min(max(128, k), 512)

    def fits(bm, bn, bk):
        x_b = bm * bk * esize
        w_b = (bk // 4) * bn            # uint8
        unpack_b = 2 * bk * bn * 4      # int32 fields + f32 weights
        acc_b = bm * bn * 4
        out_b = bm * bn * esize
        return x_b + w_b + unpack_b + acc_b + out_b <= vmem_budget

    while not fits(bm, bn, bk) and bk > 128:
        bk //= 2
    while not fits(bm, bn, bk) and bn > 128:
        bn //= 2
    while not fits(bm, bn, bk) and bm > 8:
        bm //= 2
    return bm, bn, bk


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, block_k: int,
            out_dtype):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (block_k // 4, block_n) packed bytes, widened to int32: Mosaic has
    # no vector shifts on 8-bit lanes.
    b = w_ref[...].astype(jnp.int32)
    # Unpack 4 ternary weights per byte: value (j*4+i, n) lives in bits
    # [2i, 2i+2) of byte (j, n), biased by +1 (see core.ternary.pack2bit).
    parts = [(b >> (2 * i)) & 0x3 for i in range(4)]
    wq = jnp.stack(parts, axis=1)                      # (bk//4, 4, bn)
    wq = wq.reshape(block_k, b.shape[1])               # (bk, bn)
    w_deq = (wq.astype(jnp.float32) - 1.0).astype(x_ref.dtype)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_deq,
        dimension_numbers=(((1,), (0,)), ((), ())),
        # Full f32 passes for f32 activations (they are not bf16-exact);
        # bf16 x bf16 products are exact at the default.
        precision=(jax.lax.Precision.HIGHEST
                   if x_ref.dtype == jnp.float32 else None),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _epilogue():
        scale = s_ref[...].astype(jnp.float32)          # (1, bn)
        o_ref[...] = (acc_ref[...] * scale).astype(out_dtype)


def ternary_matmul_pallas(
    x: jnp.ndarray,
    w_packed: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    vmem_budget: int = _DEF_VMEM_BUDGET,
) -> jnp.ndarray:
    """out = x @ unpack(w_packed) * scale. See module docstring for layout."""
    interpret = use_interpret(interpret)
    m, k = x.shape
    kp, n = w_packed.shape
    if kp * 4 != k:
        raise ValueError(f"w_packed rows {kp} != K/4 = {k // 4}")
    scale = scale.reshape(1, n)

    bm, bn, bk = choose_blocks_tmm(m, n, k, x.dtype, vmem_budget)
    if block_m is not None:
        bm = block_m
    if block_n is not None:
        bn = block_n
    if block_k is not None:
        bk = block_k
    if bk % 4:
        raise ValueError("block_k must be a multiple of 4")

    # Pad to block multiples; zero K padding contributes 0 (x rows are 0),
    # ternary padding bytes encode +1 each but meet zero activations.
    mp, np_, kp_ = (-m) % bm, (-n) % bn, (-k) % bk
    if mp or kp_:
        x = jnp.pad(x, ((0, mp), (0, kp_)))
    if kp_ or np_:
        w_packed = jnp.pad(w_packed, ((0, kp_ // 4), (0, np_)),
                           constant_values=0x55)  # 0x55 = four '+0' fields
    if np_:
        scale = jnp.pad(scale, ((0, 0), (0, np_)))
    mm, nn, kk = m + mp, n + np_, k + kp_

    grid = (mm // bm, nn // bn, kk // bk)
    kernel = functools.partial(_kernel, block_k=bk, out_dtype=x.dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk // 4, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ternary_matmul",
    )(x, w_packed, scale)
    return out[:m, :n]
