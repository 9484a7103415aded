#!/usr/bin/env python3
"""How completely the program's own spans explain a cell's host step.

    python3 bench/span_report.py --workload <name> --seed <n> \
        [--seconds 10] [--out <file.json>]

Serves the cell once with the profiler on, as ``bench/run.py --trace 1``
does, and prints one JSON object: the cell's per-layer metrics; the mean
program ``step`` span beside ``step_call_ms`` (the benchmark's own span
around the same calls); the share of ``step`` time its child spans
cover; per span name, the time and value per step; chip 0's idle time
per innermost program span, over the window and over the part of it
inside the benchmark's ``step`` spans; and the longest idle gap with
what covered it. Needs a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def intersect(a, b):
    """The overlap of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def shares(by_span, total):
    return {n: 100.0 * t / total for n, t in
            sorted(by_span.items(), key=lambda kv: -kv[1])} if total else {}


def report(run) -> dict:
    from bench.lib import cells, program_spans as ps, stats
    cols = ps.window_spans(run.record)
    step = cols["name"] == ps.STEP
    took = cols["end_ns"] - cols["start_ns"]
    n = int(step.sum())
    per_name = {}
    for name in sorted(set(cols["name"])):
        sel = cols["name"] == name
        per_name[name] = {"count": int(sel.sum()),
                          "ms_per_step": float(took[sel].sum()) / 1e6 / n,
                          "value_per_step": float(cols["value"][sel].sum())
                          / n}
    child = cols["parent"] == ps.STEP
    out = {"metrics": cells.read_metrics(run.cell.per_layer, run),
           "steps": n,
           "step_call_ms": stats.step_call_ms(run.record),
           "program_step_ms": float(took[step].mean()) / 1e6,
           "child_cover": float(took[child].sum() / took[step].sum()),
           "per_name": per_name}
    window = ps.trace_window(run.trace)
    offset = ps.clock_offset_ns(run.trace, run.record)
    spans = ps.on_trace(ps.ring(run.record), offset)
    idle = ps.idle_intervals(run.trace, window)
    idle_ns = sum(b - a for a, b in idle)
    in_steps = intersect(idle, ps.outermost(run.trace.host_spans, ps.STEP))
    by_span = ps.attribute(in_steps, spans)
    in_step_ns = sum(by_span.values())
    out["idle_share"] = 100.0 * idle_ns / (window[1] - window[0])
    out["idle_by_span"] = shares(ps.attribute(idle, spans),
                                 window[1] - window[0])
    out["idle_in_steps_share_of_idle"] = 100.0 * in_step_ns / idle_ns
    out["idle_in_steps_by_span"] = shares(by_span, in_step_ns)
    out["idle_in_steps_explained"] = 100.0 * (
        1 - (by_span.get(ps.STEP, 0) + by_span.get(ps.OTHER, 0))
        / in_step_ns) if in_step_ns else None
    gap = max(idle, key=lambda g: g[1] - g[0])
    gcs = [(a, b) for a, b, name in spans if name == "gc"]
    out["longest_gap"] = {
        "ms": (gap[1] - gap[0]) / 1e6,
        "by_span": shares(ps.attribute([gap], spans), gap[1] - gap[0]),
        "gc_ms": sum(b - a for a, b in intersect([gap], sorted(gcs))) / 1e6}
    out["gc"] = {"count": len(gcs), "longest_ms": max(
        [(b - a) / 1e6 for a, b in gcs], default=0.0)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench.lib import cells, harness, runner, trace, traffic, work
    from repro.compile_cache import enable_compile_cache
    cell = cells.cell(args.workload)
    try:
        devs = runner.devices(cell.chips)
    except runner.NoChip as e:
        print(f"bench/span_report.py: {e}", file=sys.stderr)
        return 1
    enable_compile_cache()
    config, mix, arch = cell.config, cell.mix, cell.arch
    pool = traffic.make_pool(args.seed, mix, arch.sensors(config),
                             config["window_us"])
    server = harness.Server(config, cell.chips, arch,
                            arch.make_weights(args.seed, config))
    hlo = server.hlo_texts(server.warm(pool))
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        rec = harness.serve(server, mix, mix["heads"] * cell.chips,
                            args.seed, pool, args.seconds, T_PROCESS,
                            cell.chips, tdir)
        summary = trace.summarize(tdir, hlo, cell.chips, harness.WINDOW_SPAN)
    run = runner.Run(cell=cell, record=rec, trace=summary,
                     peak=work.peaks(devs[0].device_kind))
    out = {"workload": args.workload, "seed": args.seed, **report(run)}
    text = json.dumps(out)
    if args.out:
        with open(args.out, "a") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
