"""Kernels, serving: positions of K and V the decode attention passes over
for a decode row (whole key blocks up to the row's last live one) over the
positions live in its slot, summed over the decode rows of the counters'
window (delta dsa_rows_streamed / delta dsa_rows_live of
InferenceEngine.stats()). A ratio, 1 the least an in-place read can do, and
above 1 by its nature: not a share of a peak. None where the program has no
such counters."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        streamed = ml.counter_delta(run, "dsa_rows_streamed")
        live = ml.counter_delta(run, "dsa_rows_live")
    except KeyError:
        return None
    return streamed / live if live else None
