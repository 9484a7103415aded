#!/usr/bin/env python3
"""Find the knee of a fixed-rate cell on the chip.

    python3 bench/sweep.py --workload <name> --heads 64,128,... \
        [--seed 1] [--seconds 8] [--out <file.jsonl>]

Serves the cell's traffic mix at each head count per chip, in one
process, and prints one JSON line per point: the offered and completed
windows per second, p50 and p95 window latency, how many windows were
still open when the window closed, and whether the point holds. A point
holds when the backlog does not grow: at least 95% of the offered
windows complete inside the window, and no more than two windows per
head are still open at its close. The knee is the highest count that
holds; the cell's mix gets 4/5 of it, written into its file by hand.
(The 300 ms window period is reported beside each point, not required:
on a TPU v5 lite no head count of the stateful cells met it, see
PERF.md.) Needs a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

HOLD_DONE_SHARE = 0.95
HOLD_OPEN_PER_HEAD = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--heads", required=True,
                    type=lambda s: [int(v) for v in s.split(",") if v])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench.lib import cells, runner
    cell = cells.cell(args.workload)
    deadline_ms = cell.mix["period_ms"]
    knee = None
    for heads in args.heads:
        try:
            out = runner.run_cell(cell, args.seed, args.seconds, False,
                                  time.perf_counter(), heads=heads)
        except runner.NoChip as e:
            print(f"bench/sweep.py: {e}", file=sys.stderr)
            return 1
        load = out["load"]
        p95 = out["metrics"].get("window_latency_p95_ms", {}).get("value")
        p50 = out["metrics"].get("window_latency_p50_ms", {}).get("value")
        holds = (load["done_per_s"] >= HOLD_DONE_SHARE * load["due_per_s"]
                 and load["open_at_close"] <= HOLD_OPEN_PER_HEAD * heads)
        if holds:
            knee = heads
        line = json.dumps({"workload": cell.name, "heads": heads,
                           "p50_ms": p50, "p95_ms": p95,
                           "p95_within_period": (p95 is not None
                                                 and p95 <= deadline_ms),
                           "holds": holds,
                           "correct": out["correct"], **load})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    print(json.dumps({"workload": cell.name, "knee": knee,
                      "p80": None if knee is None else int(0.8 * knee)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
