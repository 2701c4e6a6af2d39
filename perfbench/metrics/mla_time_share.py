"""Model step: the device time inside the latent attention's two forms (the
scopes `mla_attend`, a tile against its layer of the scratch, expanded, and
`mla_row`, a decode row against its slot's latents, absorbed; each with the
norm and rotary of the latents it makes, its up-projections and its write)
in the executions of both step programs in the traced slice, over the
device time of those executions, in per cent, as metrics/win_time_share.py
reads the sliding layers' attention. The query's and the latent's
down-projections and the output projection are outside the scopes. None
where the trace or the scopes are absent."""
from perfbench.metrics.win_time_share import scopes_time_share


def read(run):
    return scopes_time_share(run, ("mla_attend", "mla_row"))
