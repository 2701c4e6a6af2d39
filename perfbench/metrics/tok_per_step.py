"""Engine: decoded tokens per engine iteration over the counters' window,
from InferenceEngine.stats() (delta tokens_generated / delta steps)."""
from perfbench import metrics_lib as ml


def read(run):
    steps = ml.counter_delta(run, "steps")
    if not steps:
        return None
    return ml.counter_delta(run, "tokens_generated") / steps
