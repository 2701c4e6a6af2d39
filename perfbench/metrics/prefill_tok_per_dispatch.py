"""Engine: real prompt tokens per prefill dispatch over the counters'
window, from InferenceEngine.stats() (delta prefill_tokens / delta
prefill_dispatches). None where the program has no such counters."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        dispatches = ml.counter_delta(run, "prefill_dispatches")
        tokens = ml.counter_delta(run, "prefill_tokens")
    except KeyError:            # a program from before the counters
        return None
    return tokens / dispatches if dispatches else None
