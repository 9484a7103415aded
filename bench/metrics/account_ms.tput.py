"""Mean host time per StreamEngine.step() started in the measured window
that the program spent accounting collected windows (its ``account``
spans: the engines' per-slot Kraken accounting and the serving layer's
per-stream stats)."""
from bench.lib import program_spans


def read(run):
    return program_spans.per_step_ms(run.record, ["account"])
