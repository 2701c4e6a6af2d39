"""Model step: the device time inside the sliding-window layers' attention
(the scopes `win_attend`, a tile against its ring, and `win_row`, a decode
row against its slot's; each with its write into the ring) in the
executions of both step programs in the traced slice, over the device time
of those executions, in per cent. The projections, the norms and the gate
are outside the scopes. None where the trace or the scopes are absent."""
from perfbench.metrics.ssm_time_share import scope_time_share


def scopes_time_share(run, scopes):
    """The scopes' shares of both programs' device time, summed (the
    scopes do not nest)."""
    shares = [scope_time_share(run, s) for s in scopes]
    shares = [x for x in shares if x is not None]
    return sum(shares) if shares else None


def read(run):
    return scopes_time_share(run, ("win_attend", "win_row"))
