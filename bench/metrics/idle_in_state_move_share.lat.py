"""Share of the traced window in which chip 0 was idle while the
innermost of the program's spans was ``state_gather`` or
``state_park``."""
from bench.lib import program_spans


def read(run):
    return program_spans.idle_share(run, ["state_gather", "state_park"])
