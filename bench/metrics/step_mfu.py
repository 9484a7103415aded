"""Model FLOPs per window (bench/lib/work.py) times the windows
completed per second in the traced window, over the chips' peak."""
from bench.lib import stats


def read(run):
    return stats.step_mfu(run)
