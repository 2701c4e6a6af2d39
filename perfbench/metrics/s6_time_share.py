"""Model step: the device time inside the Mamba-1 layers' recurrence (the
scopes `s6_scan`, a tile from a state, and `s6_step`, a decode row a slot;
each with its write of the state into the pool) in the executions of both
step programs in the traced slice, over the device time of those
executions, in per cent. The projections, the convolution and the gate are
outside the scopes. None where the trace or the scopes are absent."""
from perfbench.metrics.win_time_share import scopes_time_share


def read(run):
    return scopes_time_share(run, ("s6_scan", "s6_step"))
