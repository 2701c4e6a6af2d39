"""Model step: the device time inside the differential attention (the
scopes `diff_attend`, a tile against its ring or against the one cache by
position, and `diff_row`, a decode row against its slot's; each with its
write into the ring, and for the cross layers the read of another layer's
cache) in the executions of both step programs in the traced slice, over
the device time of those executions, in per cent. The projections, the
difference and the pair norm are outside the scopes. None where the trace
or the scopes are absent."""
from perfbench.metrics.win_time_share import scopes_time_share


def read(run):
    return scopes_time_share(run, ("diff_attend", "diff_row"))
