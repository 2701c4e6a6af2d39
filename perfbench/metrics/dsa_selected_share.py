"""Model step: K/V rows the decode attention read over the rows live in
its slots, summed over the decode rows of the counters' window (delta
dsa_rows_read / delta dsa_rows_live of InferenceEngine.stats(): sum of
min(len, topk) over sum of len), in per cent: how much of the cache the
selection spares. None where the program has no such counters."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        read_ = ml.counter_delta(run, "dsa_rows_read")
        live = ml.counter_delta(run, "dsa_rows_live")
    except KeyError:
        return None
    return read_ / live * 100.0 if live else None
