"""Executables the program compiled (its ``compile`` spans) that started
in the measured window; every shape is warmed before it, so 0."""
from bench.lib import program_spans


def read(run):
    return program_spans.count(run.record, "compile")
