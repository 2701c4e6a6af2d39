"""Kernels, serving: places of a sliding layer's ring that the decode
attention passes over for a decode row (whole key blocks up to the slot's
last live one: the whole ring once the slot has filled it) over the
window's positions the row attends, min(length, window), summed over the
decode rows of the counters' window (delta win_rows_streamed / delta
win_rows_live of InferenceEngine.stats()). A ratio, 1 the least an in-place
read can do; past the window it is ring / window. None where the program
has no such counters."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        streamed = ml.counter_delta(run, "win_rows_streamed")
        live = ml.counter_delta(run, "win_rows_live")
    except KeyError:
        return None
    return streamed / live if live else None
