"""One whole run of a cell, from weights to the result line."""
from __future__ import annotations

import dataclasses
import gc
import sys
import tempfile
from typing import Optional

from bench.lib import cells, check, harness, stats, traffic, work
from bench.lib.cells import Cell


@dataclasses.dataclass
class Run:
    """What a metric reader gets (``bench/metrics/<name>.py``)."""
    cell: Cell
    record: harness.Record
    trace: Optional[object] = None      # trace.Summary of a traced run
    peak: Optional[dict] = None         # work.peaks(device_kind)

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def chips(self) -> int:
        return self.cell.chips

    @property
    def arch(self):
        """The configuration's adapter (``bench/arch/<arch>.py``)."""
        return self.cell.arch


class NoChip(RuntimeError):
    pass


def devices(chips: int):
    """The cell's chips; :class:`NoChip` unless JAX sees that many TPUs."""
    import jax
    found = jax.devices()
    if found[0].platform != "tpu" or len(found) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(found)} {found[0].platform} device(s)")
    return found[:chips]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, *, chip: bool = True,
             heads: Optional[int] = None, control: bool = False) -> dict:
    """Set up, serve, check; the result line as a dict. ``chip=False``
    skips the look for a TPU (the CPU rehearsal and the tests);
    ``heads`` overrides the mix's heads per chip (the knee sweep);
    ``control`` adds the control's readings: the reference at the
    precision below the configuration's, in the program's place, on the
    same sample (calibration only)."""
    import time
    marks = [("imports", time.perf_counter())]
    import jax
    from repro.compile_cache import enable_compile_cache
    if chip:
        devs = devices(cell.chips)
        enable_compile_cache()
    else:
        devs = jax.devices()[:cell.chips]
    marks.append(("devices", time.perf_counter()))
    config, mix, arch = cell.config, cell.mix, cell.arch
    params = arch.make_weights(seed, config)
    marks.append(("weights", time.perf_counter()))
    pool = traffic.make_pool(seed, mix, arch.sensors(config),
                             config["window_us"])
    marks.append(("pool", time.perf_counter()))
    server = harness.Server(config, cell.chips, arch, params)
    keys = server.warm(pool)
    marks.append(("warm", time.perf_counter()))
    # Where set-up goes, phase by phase (seconds; ``imports`` runs from
    # process start), on standard error.
    print("[setup] " + " ".join(
        f"{n}={t - (marks[i - 1][1] if i else t_process):.3f}"
        for i, (n, t) in enumerate(marks)), file=sys.stderr)
    hlo = server.hlo_texts(keys) if trace else None
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        rec = harness.serve(
            server, mix, (heads or mix["heads"]) * cell.chips, seed, pool,
            seconds, t_process, cell.chips, tdir if trace else None)
        del server
        gc.collect()
        summary = peak = None
        if trace:
            from bench.lib import trace as tr_
            summary = tr_.summarize(tdir, hlo, cell.chips,
                                    harness.WINDOW_SPAN)
            peak = work.peaks(devs[0].device_kind)
    verdict = check.check(rec, mix, config, seed, pool, arch, params,
                          control=control)
    run = Run(cell=cell, record=rec, trace=summary, peak=peak)
    due = rec.due_in_window()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": rec.memory_peak_bytes}
    out = {"correct": verdict["ok"], "attempted": len(due),
           "failed": sum(1 for w in due if w.status != "ok"),
           "metrics": cells.read_metrics(
               cell.per_layer if trace else cell.end_to_end, run),
           "device": device}
    late = [w for w in rec.windows.values()
            if w.submit is not None and w.submit < rec.t_w1
            and (w.done is None or w.done >= rec.t_w1)]
    out["load"] = {"heads": (heads or mix["heads"]) * cell.chips,
                   "due_per_s": len(due) / seconds,
                   "done_per_s": stats.completed_per_s(rec),
                   "open_at_close": len(late),
                   "steps": len(rec.steps),
                   "step_ms_p10_p50_p90": stats.step_ms_quantiles(rec)}
    out["readings"] = verdict["readings"]
    if control:
        out["control"] = verdict["control"]
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["compared"] = verdict["table"]
    print(f"[check] windows={verdict['windows']} readings="
          f"{verdict['readings']}", file=sys.stderr)
    return out
