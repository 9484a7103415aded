"""Operations and bytes the algorithm needs, from the configuration's
sizes, and the table of device peaks.

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
from typing import Dict, List

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


def _sizes(net: dict):
    h0, w0 = net["height"] // net["pool0"], net["width"] // net["pool0"]
    return h0, w0, (h0 // 4) * (w0 // 4) * net["conv2_features"]


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


def event_kernels(net: dict, b: int) -> Dict[str, List[Dict[str, float]]]:
    """Per step of ``b`` slots: each call of the served event wing's
    Pallas kernels, by kernel (conv1 and conv2 through ``lif_scan``,
    fc1 and fc2 through ``fc_lif_scan``)."""
    t = net["time_bins"]
    h0, w0, flat = _sizes(net)
    return {
        "lif_scan": [
            lif_scan(t, b, h0 * w0 * net["conv1_features"]),
            lif_scan(t, b, (h0 // 2) * (w0 // 2) * net["conv2_features"])],
        "fc_lif_scan": [
            fc_lif_scan(t, b, flat, net["hidden"]),
            fc_lif_scan(t, b, net["hidden"], net["num_classes"])]}


def snn_flops(net: dict) -> float:
    """Model FLOPs of one event window: the SCNN's convolutions and fully
    connected layers, dense, over T steps."""
    t = net["time_bins"]
    h0, w0, flat = _sizes(net)
    conv1 = 2 * h0 * w0 * net["conv1_features"] * 9 * net["in_channels"]
    conv2 = (2 * (h0 // 2) * (w0 // 2) * net["conv2_features"] * 9
             * net["conv1_features"])
    fc = 2 * flat * net["hidden"] + 2 * net["hidden"] * net["num_classes"]
    return float(t * (conv1 + conv2 + fc))


def tcn_flops(net: dict) -> float:
    """Model FLOPs of one frame through the CUTIE network."""
    h0, w0, flat = _sizes(net)
    conv1 = 2 * h0 * w0 * net["conv1_features"] * 9 * net["in_channels"]
    conv2 = (2 * (h0 // 2) * (w0 // 2) * net["conv2_features"] * 9
             * net["conv1_features"])
    return float(conv1 + conv2 + 2 * flat * net["hidden"]
                 + 2 * net["hidden"] * net["num_classes"])


def window_flops(config: dict) -> float:
    """Model FLOPs of one served window (a fused tick: both wings)."""
    f = snn_flops(config["snn"])
    if "tcn" in config:
        f += tcn_flops(config["tcn"])
    return f


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
