"""Kernels, serving: positions of the ONE cache by position that the decode
attention passes over for a decode row (whole key blocks, up to the slot's
last live one where the pool kernel reads, up to the longest slot's where
the XLA loop does) over the positions the row attends, each summed over
the layers that read that cache (the full layer and the cross layers
behind it) and over the decode rows of the counters' window (delta
xkv_rows_streamed / delta xkv_rows_live of InferenceEngine.stats()). A
ratio, 1 the least an in-place read can do. None where the program has no
such counters."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        streamed = ml.counter_delta(run, "xkv_rows_streamed")
        live = ml.counter_delta(run, "xkv_rows_live")
    except KeyError:
        return None
    return streamed / live if live else None
