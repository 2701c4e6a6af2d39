"""Engine: steps whose first program was issued while the tokens the step
before had decided were still undelivered (they reached their consumers
under this step's program), of all steps of the counters' window, in per
cent (delta issued_ahead / delta steps of InferenceEngine.stats()). None
where the program has no such counter."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        ahead = ml.counter_delta(run, "issued_ahead")
        steps = ml.counter_delta(run, "steps")
    except KeyError:
        return None
    return ahead / steps * 100.0 if steps else None
