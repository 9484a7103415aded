"""Device time of the event wing's step program (voxelize, SCNN,
readout) per execution, from the trace, averaged over the chips."""


def read(run):
    return None if run.trace is None else run.trace.module_ms("event")
