"""Kernels, serving: positions of latents that the decode attention passes
over for a decode row (whole key blocks up to the LONGEST live slot's last,
for every row of the step: the XLA loop of models/latent_attention.py) over
the positions live, the row's own among them, summed over the decode rows of
the counters' window (delta mla_rows_streamed / delta mla_rows_live of
InferenceEngine.stats()). A ratio, 1 the least an in-place read can do. None
where the program has no such counters."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        streamed = ml.counter_delta(run, "mla_rows_streamed")
        live = ml.counter_delta(run, "mla_rows_live")
    except KeyError:
        return None
    return streamed / live if live else None
