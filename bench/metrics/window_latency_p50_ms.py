"""Median over every window due in the measured window of the time from
its due time to the step() that handed back its PWM."""
from bench.lib import stats


def read(run):
    return stats.percentile(stats.latencies_ms(run.record), 50)
