"""Pallas TPU kernels for ColibriES's two accelerator analogues.

  lif_scan.py       -- SNE: fused LIF temporal scan (VMEM-resident state)
  fc_lif_scan.py    -- SNE: fused synapse(matmul)+LIF scan for fc layers
                       (weights + membrane VMEM-resident; currents never
                       reach HBM)
  ternary_matmul.py -- CUTIE: packed 2-bit ternary GEMM (dequant-in-kernel)
  wkv6_scan.py      -- RWKV-6 WKV recurrence (state-resident scan; the SNE
                       insight applied to the rwkv6-7b assigned arch)
  ops.py            -- jit'd differentiable wrappers (public API)
  ref.py            -- pure-jnp oracles (tests assert_allclose against them)
  backend.py        -- use_interpret: the one rule for compiled vs
                       interpret mode (interpret exactly when not on TPU)

All kernels are written for TPU (pl.pallas_call + BlockSpec VMEM tiling),
validated against their oracles in interpret mode on CPU, and compiled
for a described TPU v5e at Table II widths by tests/test_tpu_compile.py.
"""
from repro.kernels.ops import (fc_lif_scan, fc_lif_scan_batched, lif_scan,
                               lif_scan_batched, pack_ternary_weights,
                               ternary_matmul)
from repro.kernels.ref import lif_scan_ref, ternary_matmul_ref, wkv6_ref
from repro.kernels.wkv6_scan import wkv6_scan_pallas

__all__ = [
    "lif_scan", "lif_scan_batched", "fc_lif_scan", "fc_lif_scan_batched",
    "pack_ternary_weights", "ternary_matmul",
    "lif_scan_ref", "ternary_matmul_ref", "wkv6_ref", "wkv6_scan_pallas",
]
