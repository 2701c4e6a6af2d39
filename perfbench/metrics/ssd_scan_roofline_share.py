"""Kernels, serving: the least time the state-space layers' chunked scan
over the traced tiles could take on this chip (the larger of its FLOPs over
the peak and its bytes over the peak bandwidth, counted from the
recurrence, 4 P N FLOPs a token a head and the state in and out once a
tile, by the family's `ssd_scan_flops` / `ssd_scan_bytes` over every layer,
whatever form computes it) over the device time inside the `ssd_scan` scope
of the tile program's executions in the trace. A tile counts its mean REAL
tokens over the counters' window. None where the trace, the scope or the
family's counts are absent."""
from perfbench import scope_times, spec, yardstick

SCOPE, PROGRAM = "ssd_scan", "jit_prefill"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "ssd_scan_flops"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    tokens = scope_times.tile_tokens(run)
    if got is None or not tokens:
        return None
    seconds, runs = got
    peaks = yardstick.peaks(run["device"]["kind"])
    floor_s = runs * max(
        family.ssd_scan_flops(cfg, tokens) / peaks["flops_per_s"],
        family.ssd_scan_bytes(cfg, tokens, 2.0) / peaks["bytes_per_s"])
    return floor_s / seconds * 100.0
