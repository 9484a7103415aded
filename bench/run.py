#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name through ``BENCHMARK.json`` (see ``bench/lib/cells.py``). The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; last comes ``compared``, each number of the correctness
check beside its limit, and those also end standard error. Exits 1
without a result when JAX finds no TPU or fewer chips than the cell
asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    from bench.lib import cells, runner
    cell = cells.cell(args.workload)
    try:
        out = runner.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS)
    except runner.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    for name, c in out["compared"].items():
        print(f"compared {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
