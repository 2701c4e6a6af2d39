"""Model step: the device time inside the full-attention layers' attention
(the scopes `att_attend`, a tile against its scratch by position, and
`att_row`, a decode row against its slot) over the device time of both step
programs in the traced slice, in per cent, as metrics/win_time_share.py
reads the sliding layers'."""
from perfbench.metrics.win_time_share import scopes_time_share


def read(run):
    return scopes_time_share(run, ("att_attend", "att_row"))
