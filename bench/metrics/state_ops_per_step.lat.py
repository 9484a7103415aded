"""Mean number of eager array operations per StreamEngine.step() started
in the measured window that the program issued to move carried state
(the summed values of its ``state_gather`` and ``state_park`` spans)."""
from bench.lib import program_spans


def read(run):
    return program_spans.per_step_value(run.record,
                                        ["state_gather", "state_park"])
