"""Model step: rows the expert matmuls computed over the picks that landed
on a held expert, both programs, over the counters' window (delta
moe_rows_computed / delta moe_local_picks of InferenceEngine.stats()).
Only the rows a request owns are counted as picks (a prefill tile's padded
tail is routed nowhere). 1.0 is the floor; a capacity of the group's
length would read experts / experts per token. None where the program has
no such counters (an engine whose layer holds every expert has none: both
counts would be the shapes')."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        rows = ml.counter_delta(run, "moe_rows_computed")
        picks = ml.counter_delta(run, "moe_local_picks")
    except KeyError:
        return None
    return rows / picks if picks else None
