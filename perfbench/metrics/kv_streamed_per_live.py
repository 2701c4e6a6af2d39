"""Kernels, serving: positions of K and V the dense model's decode attention
passes over for a decode row (whole key blocks up to the row's last live
one, and its own) over the positions the row attends, summed over the
decode rows of the counters' window (delta kv_rows_streamed / delta
kv_rows_live of InferenceEngine.stats()). A ratio, 1 the least an in-place
read can do, and above 1 by its nature: not a share of a peak. None where
the program has no such counters (one whose rows attend a layer sliced out
of the pool, `max_len` positions a row whatever its length)."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        streamed = ml.counter_delta(run, "kv_rows_streamed")
        live = ml.counter_delta(run, "kv_rows_live")
    except KeyError:
        return None
    return streamed / live if live else None
