"""Model step: the device time inside the expert layers (the scopes
`moe_router`, scores, choice and weights; `moe_shared`, the shared expert;
`moe_experts`, the held experts' matmuls) over the device time of both step
programs in the traced slice, in per cent, as metrics/win_time_share.py
reads the sliding layers' attention. The dispatch and combine products lie
outside the scopes."""
from perfbench.metrics.win_time_share import scopes_time_share


def read(run):
    return scopes_time_share(run, ("moe_router", "moe_shared",
                                   "moe_experts"))
