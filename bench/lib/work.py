"""Operations and bytes of each served Pallas kernel's call, and the
table of device peaks. Which calls a step makes, at which sizes, and a
window's model FLOPs are the configuration adapter's
(``bench/arch/<arch>.py``: ``kernel_work``, ``window_flops``), built
from the functions here.

Counts are of the algorithm at its unpadded shapes, not of what a kernel
happens to move: a padded or re-read operand costs the kernel time
without raising its count, so a roofline share can only read low, never
above 100%. A multiply-add counts as 2 operations. The LIF update
``v = alpha * v * live + i`` counts 3 per neuron and step.

The least time of a kernel call is the largest of its operations over
the MXU's bfloat16 peak, the bytes it reads over the vector memory's
read bandwidth and the bytes it writes over its write bandwidth. The
compiler keeps the served kernels' operands in vector memory (the
``S(1)`` memory space in the compiled HLO), the fastest place data can
be, so no placement makes a call faster than this. The program runs
its float32 matmuls at HIGHEST precision, six bfloat16 passes per
product, so a float32 matmul reaches at most a sixth of the MXU peak.
"""
from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = os.path.join(os.path.dirname(HERE), "peaks.json")
F32 = 4
LIF_OPS = 3


def peaks(device_kind: str, path: str = PEAKS) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def lif_scan(t: int, b: int, neurons: int) -> Dict[str, float]:
    """One LIF scan over (T, B, neurons) currents: currents and the
    initial membrane read, spikes and the final membrane written."""
    return {"ops": LIF_OPS * t * b * neurons,
            "read": F32 * (t * b * neurons + b * neurons),
            "write": F32 * (t * b * neurons + b * neurons)}


def fc_lif_scan(t: int, b: int, k: int, n: int) -> Dict[str, float]:
    """Fused (T, B, K) spikes @ (K, N) weights + LIF: spikes, weights
    (once) and the initial membrane read, spikes and the final membrane
    written."""
    return {"ops": 2 * t * b * k * n + LIF_OPS * t * b * n,
            "read": F32 * (t * b * k + k * n + b * n),
            "write": F32 * (t * b * n + b * n)}


def _times(work: Dict[str, float], peak: Dict[str, float]):
    return {"compute": work["ops"] / peak["flops_per_s"],
            "memory read": work["read"] / peak["vmem_read_bytes_per_s"],
            "memory write": work["write"] / peak["vmem_write_bytes_per_s"]}


def roofline_s(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take for ``work``."""
    return max(_times(work, peak).values())


def bound(work: Dict[str, float], peak: Dict[str, float]) -> str:
    """Which peak bounds ``work``: compute, memory read or memory write."""
    t = _times(work, peak)
    return max(t, key=t.get)
