"""Kernels, serving: the least time the delta-rule layers' recurrence over
the traced tiles could take on this chip (the larger of its FLOPs over the
peak and its bytes over the peak bandwidth, by the family's `kda_scan_flops`
/ `kda_scan_bytes` over every such layer: the blocked form's matrix products
at chunks of 64 rows, 6 x 128^2 + 6 x 64 x 128 a row a head; a row's q, k, v
and g in and its output out, the state in and out once a tile; the same work
whatever form computes it) over the device time inside the `kda_scan` scope
of the tile program's executions in the trace, as
metrics/s6_scan_roofline_share.py counts its tiles: a tile counts its mean
REAL tokens over the counters' window. None where the trace, the scope or
the family's counts are absent."""
from perfbench import scope_times, spec, yardstick

SCOPE, PROGRAM = "kda_scan", "jit_prefill"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "kda_scan_flops"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    tokens = scope_times.tile_tokens(run)
    if got is None or not tokens:
        return None
    seconds, runs = got
    peaks = yardstick.peaks(run["device"]["kind"])
    floor_s = runs * max(
        family.kda_scan_flops(cfg, tokens) / peaks["flops_per_s"],
        family.kda_scan_bytes(cfg, tokens, 2.0) / peaks["bytes_per_s"])
    return floor_s / seconds * 100.0
