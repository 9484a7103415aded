"""Mean number of state-move programs per StreamEngine.step() started in
the measured window: the summed values of the program's
``state_gather`` and ``state_park`` spans. One compiled program moves a
step's carried state, so this is the share of steps that move it."""
from bench.lib import program_spans


def read(run):
    return program_spans.per_step_value(run.record,
                                        ["state_gather", "state_park"])
