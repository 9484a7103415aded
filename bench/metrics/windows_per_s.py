"""Windows (fused ticks for fused heads) completed ok inside the
measured window, over its length, summed over the cell's chips."""
from bench.lib import stats


def read(run):
    return stats.completed_per_s(run.record)
