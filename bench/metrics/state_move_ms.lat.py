"""Mean host time per StreamEngine.step() started in the measured window
that the program spent moving carried state between slots through its
state-move programs (its ``state_gather`` and ``state_park`` spans)."""
from bench.lib import program_spans


def read(run):
    return program_spans.per_step_ms(run.record,
                                     ["state_gather", "state_park"])
