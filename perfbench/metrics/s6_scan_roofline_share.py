"""Kernels, serving: the least time the Mamba-1 layers' recurrence over the
traced tiles could take on this chip (the larger of its FLOPs over the peak
and its bytes over the peak bandwidth, counted from the recurrence, 7
operations an element of the state a row, a row's inputs in and its output
out and the state and tail in and out once a tile, by the family's
`s6_scan_flops` / `s6_scan_bytes` over every such layer, whatever form
computes it) over the device time inside the `s6_scan` scope of the tile
program's executions in the trace. The yardstick has no vector peak: the
recurrence is elementwise and no matmul, so its FLOPs over the MATRIX peak
are a fiftieth of its bytes' time and the share reads against the
bandwidth, low for any form. A tile counts its mean REAL tokens over the
counters' window. None where the trace, the scope or the family's counts
are absent."""
from perfbench import scope_times, spec, yardstick

SCOPE, PROGRAM = "s6_scan", "jit_prefill"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "s6_scan_flops"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    tokens = scope_times.tile_tokens(run)
    if got is None or not tokens:
        return None
    seconds, runs = got
    peaks = yardstick.peaks(run["device"]["kind"])
    floor_s = runs * max(
        family.s6_scan_flops(cfg, tokens) / peaks["flops_per_s"],
        family.s6_scan_bytes(cfg, tokens, 2.0) / peaks["bytes_per_s"])
    return floor_s / seconds * 100.0
