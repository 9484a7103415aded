"""95th percentile of the sample of window_latency_p50_ms."""
from bench.lib import stats


def read(run):
    return stats.percentile(stats.latencies_ms(run.record), 95)
