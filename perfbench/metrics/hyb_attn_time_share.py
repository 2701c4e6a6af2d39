"""Model step: the device time inside the `hyb_attn` scope (a block's
attention heads beside its state-space branch: their projections and
attention over the cache) over the device time of both step programs in
the traced slice, in per cent, as metrics/ssm_time_share.py reads
`hyb_ssm`."""
from perfbench.metrics.ssm_time_share import scope_time_share


def read(run):
    return scope_time_share(run, "hyb_attn")
