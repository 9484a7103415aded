"""From process start to the start of the measured window: imports,
device, weights, traffic pool, compile or cache load, warm traffic."""


def read(run):
    return run.record.setup_s
