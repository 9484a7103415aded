"""Share of the traced window in which chip 0 was idle while the
innermost of the program's spans was ``pack``."""
from bench.lib import program_spans


def read(run):
    return program_spans.idle_share(run, ["pack"])
