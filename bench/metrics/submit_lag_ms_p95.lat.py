"""95th percentile of how late the load generator submitted each window
due in the measured window, after its due time."""
from bench.lib import stats


def read(run):
    return stats.percentile(stats.submit_lag_ms(run.record), 95)
