"""Kernels, serving: the least time the latent attention's decode rows
could take on this chip (the larger of each LIVE slot's positions of latents
a layer over the peak bandwidth and the absorbed row's products over them
over the peak, by the family's `mla_row_bytes` / `mla_row_flops`: 1,152 B
against 64 heads x 2 x 1,088 FLOPs a position a layer, the bytes twice the
FLOPs' time on a v5e) over the device time inside the `mla_row` scope of the
decode program's executions in the trace, as
metrics/win_row_roofline_share.py weighs its live slots: the requests'
records', each at its mean length in the traced slice and weighed by the
share of the slice it was decoding (the program steps every slot, live or
not, so the share reads low while slots are empty, never high). None where
the trace, the scope or the family's count is absent."""
from perfbench import metrics_lib as ml, scope_times, spec, yardstick

SCOPE, PROGRAM = "mla_row", "jit_decode"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "mla_row_bytes"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    live = ml.mean_live_tokens(run, *run["traced"])
    if got is None or not live:
        return None
    seconds, runs = got
    peaks = yardstick.peaks(run["device"]["kind"])
    floor_s = runs * max(
        family.mla_row_bytes(cfg, live, 2.0) / peaks["bytes_per_s"],
        family.mla_row_flops(cfg, live) / peaks["flops_per_s"])
    return floor_s / seconds * 100.0
