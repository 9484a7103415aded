"""From a profiler trace of the measured window to per-layer numbers.

The trace is JAX's ``.xplane.pb``, read with
``jax.profiler.ProfileData``. Each TPU is a plane ``/device:TPU:<n>``;
its line ``XLA Ops`` holds one event per device operation and its line
``XLA Modules`` one event per execution of a compiled program. Host
threads are planes ``/host:...`` whose events include the benchmark's
own spans (``step``, ``submit``, ``idle-wait``).

An operation's event is named by its HLO instruction
(``%fusion.3 = f32[...] fusion(...)``); a module's by its program
(``jit_run(<fingerprint>)``). Each wing's compiled step is given as HLO
text, whose ``HloModule <name>`` line names its program: an execution
is tagged to a wing only when its module carries one of those names.
Where several wings' steps share a name (the program compiles both the
event and the frame step as ``jit_run``), the execution goes to the
wing whose HLO declares the instructions that ran inside it. Every
other module (the serving layer's state-move program, eager
operations) stays untagged and keeps its own name, even where its
instruction names (``fusion.1``, ``copy.33``) also occur in a wing's
HLO. Each ``tpu_custom_call``'s serialized kernel body holds the source
locations it was traced from, among them its kernel file under
``kernels/`` (``lif_scan.py``, ``fc_lif_scan.py``,
``ternary_matmul.py``), which names the Pallas kernel.
"""
from __future__ import annotations

import base64
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# The name the program's step programs compile under today. The tagging
# below does not use it: it reads each wing's name from its HLO text.
# A compile test of the program checks that its event step keeps it.
STEP_MODULE = "jit_run"
HOST_SPANS = ("step", "submit", "idle-wait", "window")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_MODULE = re.compile(r"^\s*HloModule\s+([^\s,]+)", re.M)
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]*)"')
_KERNEL_FILE = re.compile(rb"/kernels/(\w+)\.py")
_NOT_KERNELS = {b"ops", b"backend", b"ref", b"__init__"}


def hlo_index(text: str) -> Tuple[set, Dict[str, str]]:
    """Instruction names of an HLO module, and its Pallas kernels:
    ``{instruction name: kernel}``, the kernel being the stem of the
    source file its custom call was written in."""
    names, kernels = set(), {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        names.add(m.group(1))
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels[m.group(1)] = _kernel_of(line)
    return names, kernels


def module_name(text: str) -> Optional[str]:
    """The program name of an HLO module's text (its ``HloModule``
    line), which its executions carry in a trace."""
    m = _MODULE.search(text)
    return m.group(1) if m else None


def _kernel_of(line: str) -> str:
    """The kernel file a ``tpu_custom_call`` was traced from."""
    body = _BODY.search(line)
    if body:
        files = set(_KERNEL_FILE.findall(base64.b64decode(body.group(1))))
        files -= _NOT_KERNELS
        if len(files) == 1:
            return files.pop().decode()
    return "pallas"


def instruction(event_name: str) -> str:
    """``fusion.3`` of ``%fusion.3 = f32[...] fusion(...)``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


@dataclasses.dataclass
class Op:
    name: str       # HLO instruction
    start: int      # ns
    end: int        # ns
    wing: Optional[str] = None      # "event", "frame", or None
    kernel: Optional[str] = None    # Pallas kernel file stem
    module: str = ""                # the program it ran in


@dataclasses.dataclass
class Chip:
    ops: List[Op]
    modules: List[Tuple[int, int, Optional[str]]]   # start, end, wing

    def clipped(self, lo: int, hi: int) -> "Chip":
        """The operations and module executions that started in
        [lo, hi), each cut to end by ``hi``."""
        ops = [dataclasses.replace(o, end=min(o.end, hi)) for o in self.ops
               if lo <= o.start < hi]
        return Chip(ops, [(a, min(b, hi), w) for a, b, w in self.modules
                          if lo <= a < hi])

    def busy_intervals(self) -> List[Tuple[int, int]]:
        merged: List[List[int]] = []
        for op in sorted(self.ops, key=lambda o: o.start):
            if merged and op.start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], op.end)
            else:
                merged.append([op.start, op.end])
        return [(a, b) for a, b in merged]


@dataclasses.dataclass
class Summary:
    window_s: float
    chips: List[Chip]
    host_spans: List[Tuple[int, int, str]]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        total = sum(b - a for c in self.chips
                    for a, b in c.busy_intervals())
        return total / len(self.chips) / 1e9

    def module_ms(self, wing: str) -> Optional[float]:
        """Device time of one execution of ``wing``'s step program: its
        operations' busy time over its executions, averaged over chips."""
        per_chip = []
        for c in self.chips:
            n = sum(1 for m in c.modules if m[2] == wing)
            if n:
                ops = Chip([o for o in c.ops if o.wing == wing], [])
                busy = sum(b - a for a, b in ops.busy_intervals())
                per_chip.append(busy / n / 1e6)
        return sum(per_chip) / len(per_chip) if per_chip else None

    def kernel_calls(self, kernel: str) -> Tuple[float, float]:
        """(calls, device seconds) of ``kernel``, per chip."""
        n = sum(1 for c in self.chips for o in c.ops if o.kernel == kernel)
        s = sum(o.end - o.start for c in self.chips for o in c.ops
                if o.kernel == kernel)
        k = len(self.chips)
        return n / k, s / k / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (chip 0), named
        ``wing:kernel`` or ``wing:op``, and the longest idle gaps on chip
        0, each named by the host span it fell in."""
        chip = self.chips[0]
        by_name: Dict[str, int] = collections.Counter()
        for o in chip.ops:
            by_name[f"{o.wing or o.module}:{o.kernel or o.name}"] += (
                o.end - o.start)
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        busy = chip.busy_intervals()
        gaps = [(a_end, b_start) for (_, a_end), (b_start, _)
                in zip(busy, busy[1:]) if b_start > a_end]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, t / 1e9] for n, t in ops],
                "idle_gaps": [[self.host_label((a + b) // 2), (b - a) / 1e9]
                              for a, b in gaps]}

    def host_label(self, t: int) -> str:
        """The innermost benchmark span covering instant ``t``."""
        best = None
        for a, b, name in self.host_spans:
            if a <= t < b and (best is None or b - a < best[1] - best[0]):
                best = (a, b, name)
        return best[2] if best else "other"


def _wing_of(op_names: set, index: Dict[str, set]) -> Optional[str]:
    """The wing whose HLO declares the most of these operation names
    that no other wing's HLO declares."""
    best, score = None, 0
    for wing, names in index.items():
        others = set().union(*(n for w, n in index.items() if w != wing))
        s = len(op_names & (names - others))
        if s > score:
            best, score = wing, s
    if best is None and len(index) == 1:
        best = next(iter(index))
    return best


def read_chips(data, hlo: Dict[str, str], chips: int) -> List[Chip]:
    """The first ``chips`` TPU planes of a trace, each operation given
    its wing and, for a Pallas call, its kernel: only executions of a
    module that one of the wings' HLO texts names are tagged."""
    index = {w: hlo_index(t) for w, t in hlo.items()}
    by_module: Dict[str, Dict[str, set]] = {}
    for w, t in hlo.items():
        by_module.setdefault(module_name(t), {})[w] = index[w][0]
    planes = sorted((p for p in data.planes if DEVICE_PLANE.search(p.name)),
                    key=lambda p: int(DEVICE_PLANE.search(p.name).group(1)))
    out = []
    for plane in planes[:chips]:
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [Op(instruction(e.name), int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                       for e in line.events]
            elif line.name == MODULES_LINE:
                modules = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                            e.name.split("(")[0]) for e in line.events]
        ops.sort(key=lambda o: o.start)
        tagged, i = [], 0
        for a, b, module in sorted(modules):
            inside = []
            while i < len(ops) and ops[i].start < b:
                if ops[i].start >= a:
                    inside.append(ops[i])
                i += 1
            wings = by_module.get(module)
            wing = (_wing_of({o.name for o in inside}, wings)
                    if wings else None)
            for o in inside:
                o.wing, o.module = wing, module
                if wing is not None:
                    o.kernel = index[wing][1].get(o.name)
            tagged.append((a, b, wing))
        out.append(Chip(ops, tagged))
    if len(out) < chips:
        raise ValueError(f"trace holds {len(out)} TPU planes, cell uses "
                         f"{chips}")
    return out


def host_spans(data) -> List[Tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for p in data.planes if p.name.startswith("/host")
            for line in p.lines for e in line.events
            if e.name in HOST_SPANS]


def load(trace_dir: str):
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(paths[-1])


def summarize(trace_dir: str, hlo: Dict[str, str], chips: int,
              window_span: str) -> Summary:
    return from_data(load(trace_dir), hlo, chips, window_span)


def from_data(data, hlo: Dict[str, str], chips: int,
              window_span: Optional[str] = None) -> Summary:
    """The summary of a read trace (``jax.profiler.ProfileData``), cut to
    the host span named ``window_span`` (the measured window; the trace
    also holds its start and its stop), or to the device operations'
    extent when no such span is given."""
    spans = host_spans(data)
    devices = read_chips(data, hlo, chips)
    marks = [(a, b) for a, b, n in spans if n == window_span]
    if marks:
        lo, hi = max(marks, key=lambda m: m[1] - m[0])
    else:
        ops = [o for c in devices for o in c.ops]
        lo, hi = min(o.start for o in ops), max(o.end for o in ops)
    return Summary(window_s=(hi - lo) / 1e9,
                   chips=[c.clipped(lo, hi) for c in devices],
                   host_spans=spans)
