#!/usr/bin/env python3
"""Readings that set the limits of a cell's correctness check.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control 1,2,3] [--seconds 4] [--heads N] [--out <file.jsonl>]

Runs the cell once per seed, in one process, at the cell's own size and
load (a short window), and prints one JSON line per seed: the readings
of the program against the reference and, for the ``--control`` seeds,
the readings of the control (the reference at the precision below the
configuration's, in the program's place) on the same sample. The lower
reading of a number is the largest the program gives over the seeds,
the upper the smallest the control gives (see PERF.md). Needs a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def _ints(s: str):
    return [int(v) for v in s.split(",") if v]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--heads", type=int, default=None,
                    help="heads per chip, in place of the mix's")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench.lib import cells, runner
    cell = cells.cell(args.workload)
    sink = open(args.out, "a") if args.out else None
    try:
        for seed in args.seeds:
            out = runner.run_cell(cell, seed, args.seconds, False,
                                  time.perf_counter(), heads=args.heads,
                                  control=seed in args.control)
            line = json.dumps({"workload": cell.name, "seed": seed,
                               "correct": out["correct"],
                               "program": out["readings"],
                               "control": out.get("control"),
                               "load": out["load"]})
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    except runner.NoChip as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 1
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
