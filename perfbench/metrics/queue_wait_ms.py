"""Engine: mean wait of a request from its submit to the step of its first
prefill span, over the requests admitted in the counters' window (delta
queue_wait_s / delta admitted of InferenceEngine.stats(): the sum of the
`queue_wait_ms` their `engine.slot` spans carry). None where the program
has no such counters."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        n = ml.counter_delta(run, "admitted")
        wait_s = ml.counter_delta(run, "queue_wait_s")
    except KeyError:
        return None
    return wait_s / n * 1e3 if n else None
