"""Device time of the frame wing's step program (CUTIE) per execution,
from the trace, averaged over the chips."""


def read(run):
    return None if run.trace is None else run.trace.module_ms("frame")
