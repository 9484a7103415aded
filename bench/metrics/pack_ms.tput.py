"""Mean host time per StreamEngine.step() started in the measured window
that the program spent packing windows into its fixed batches (its
``pack`` spans: pad_event_windows, frame padding)."""
from bench.lib import program_spans


def read(run):
    return program_spans.per_step_ms(run.record, ["pack"])
