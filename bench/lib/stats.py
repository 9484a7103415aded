"""Statistics of a run, shared by the metric readers."""
from __future__ import annotations

import math
from typing import List, Optional

from bench.lib import work

MISSED_MS = 1e6   # latency given to a window that failed or never came


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in [0, 100]); None if empty."""
    if not values:
        return None
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def latencies_ms(rec) -> List[float]:
    """Every window due in the measured window: due time to the return
    of the step() call that handed back its result. A window that failed
    or never came counts as having missed every limit."""
    out = []
    for w in rec.due_in_window():
        if w.status == "ok" and w.done is not None:
            out.append((w.done - w.due) * 1e3)
        else:
            out.append(MISSED_MS)
    return out


def completed_per_s(rec) -> float:
    """Windows (fused ticks) completed ok inside the measured window,
    over its length."""
    n = sum(1 for w in rec.windows.values()
            if w.status == "ok" and rec.in_window(w.done))
    return n / rec.seconds


def step_call_ms(rec) -> Optional[float]:
    """Mean wall time of a step() call that started in the window."""
    d = [b - a for a, b in rec.steps if rec.in_window(a)]
    return 1e3 * sum(d) / len(d) if d else None


def step_ms_quantiles(rec) -> List[float]:
    """10th, 50th and 90th percentile of the step() calls that started in
    the measured window, in ms."""
    d = [1e3 * (b - a) for a, b in rec.steps if rec.in_window(a)]
    return [percentile(d, q) for q in (10, 50, 90)] if d else []


def submit_lag_ms(rec) -> List[float]:
    """How late each window due in the window was submitted."""
    return [(w.submit - w.due) * 1e3 for w in rec.due_in_window()
            if w.submit is not None]


def roofline_share(run, kernel: str) -> Optional[float]:
    """% of its roofline that ``kernel`` reached in the traced window:
    its calls' least possible time over their measured device time, per
    chip, the work of each call being the configuration adapter's
    ``kernel_work``. None without a trace, without calls of the kernel,
    or where the adapter counts no work for it."""
    if run.trace is None or run.peak is None:
        return None
    n, seconds = run.trace.kernel_calls(kernel)
    if not n or seconds <= 0:
        return None
    calls = run.arch.kernel_work(run.config,
                                 run.config["slots_per_chip"]).get(kernel)
    if not calls:
        return None
    least = sum(work.roofline_s(c, run.peak) for c in calls)
    return 100.0 * (n / len(calls)) * least / seconds


def step_mfu(run) -> Optional[float]:
    """% of the chips' peak FLOP/s that the model's own FLOPs per window
    (the adapter's ``window_flops``), at the windows completed per second
    in the traced window, make up."""
    if run.trace is None or run.peak is None:
        return None
    rate = completed_per_s(run.record)
    return (100.0 * run.arch.window_flops(run.config) * rate
            / (run.chips * run.peak["flops_per_s"]))
