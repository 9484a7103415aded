"""Mean host time of a StreamEngine.step() call that started in the
measured window: slot policy, packing, state gather and park, dispatch
and the collect of the step before."""
from bench.lib import stats


def read(run):
    return stats.step_call_ms(run.record)
