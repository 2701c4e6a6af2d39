"""Model step: the device time inside the delta-rule linear-attention
layers' three scopes (`kda_scan`, a tile's recurrence from a state to a
state, with the state's write into the pool; `kda_step`, a decode row's,
with its write; `kda_conv`, the three short convolutions, their SiLU, the
norms of q and k and the tail's write) in the executions of both step
programs in the traced slice, over the device time of those executions, in
per cent, as metrics/ssm_time_share.py reads a state-space branch. The
projections, the gate's and beta's, the output norm and the output
projection are outside the scopes. None where the trace or the scopes are
absent."""
from perfbench.metrics.win_time_share import scopes_time_share


def read(run):
    return scopes_time_share(run, ("kda_scan", "kda_step", "kda_conv"))
