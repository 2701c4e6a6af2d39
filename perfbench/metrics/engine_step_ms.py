"""Engine: wall time per engine iteration, host work included (the
counters' window on the replica's clock over delta steps)."""
from perfbench import metrics_lib as ml


def read(run):
    steps = ml.counter_delta(run, "steps")
    if not steps:
        return None
    return ml.counter_delta(run, "t_monotonic") / steps * 1e3
