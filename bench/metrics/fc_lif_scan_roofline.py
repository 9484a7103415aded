"""Share of its roofline that the fc_lif_scan Pallas kernel reaches in the
traced window: the least time the chip could take for the kernel's
calls (operations and bytes from bench/lib/work.py, peaks from
bench/peaks.json) over their measured device time."""
from bench.lib import stats


def read(run):
    return stats.roofline_share(run, "fc_lif_scan")
