"""Kernels, serving: the least time the delta-rule layers' one-row step
could take on this chip (its bytes over the peak bandwidth: each LIVE slot's
float32 state of each layer in and out, by the family's `kda_step_bytes`;
its FLOPs are a thousandth of that time) over the device time inside the
`kda_step` scope of the decode program's executions in the trace, as
metrics/ssd_step_roofline_share.py, whose `live_rows` it uses: the requests'
records', each weighed by the share of the traced slice it was decoding (the
program steps every slot, live or not, so the share reads low while slots
are empty, never high). None where the trace, the scope or the family's
count is absent."""
from perfbench import scope_times, spec, yardstick
from perfbench.metrics.ssd_step_roofline_share import live_rows

SCOPE, PROGRAM = "kda_step", "jit_decode"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "kda_step_bytes"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    rows = live_rows(run, *run["traced"])
    if got is None or not rows:
        return None
    seconds, runs = got
    floor_s = runs * family.kda_step_bytes(cfg, rows) \
        / yardstick.peaks(run["device"]["kind"])["bytes_per_s"]
    return floor_s / seconds * 100.0
