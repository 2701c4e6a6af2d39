"""Engine: mean time from the step of a request's first prefill span to
its first token, over the first tokens of the counters' window (delta
prefill_span_s / delta first_tokens of InferenceEngine.stats()): with
`queue_wait_ms`, TTFT at the replica. None where the program has no such
counters."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        n = ml.counter_delta(run, "first_tokens")
        span_s = ml.counter_delta(run, "prefill_span_s")
    except KeyError:
        return None
    return span_s / n * 1e3 if n else None
