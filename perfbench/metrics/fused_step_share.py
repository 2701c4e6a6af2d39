"""Engine: steps in which a prefill span and live decode rows ran as ONE
program, of all steps of the counters' window, in per cent (delta
fused_steps / delta steps of InferenceEngine.stats()). None where the
program has no such counter."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        fused = ml.counter_delta(run, "fused_steps")
        steps = ml.counter_delta(run, "steps")
    except KeyError:
        return None
    return fused / steps * 100.0 if steps else None
