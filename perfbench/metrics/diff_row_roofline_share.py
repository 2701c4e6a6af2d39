"""Kernels, serving: the least time the differential attention's decode
rows could take on this chip (each LIVE slot's positions of the ONE cache
by position once for each layer that reads it, eight here, and its
min(length, window) positions once for each window layer, over the peak
bandwidth, by the family's `diff_row_bytes`; the FLOPs are a hundredth of
that time) over the device time inside the `diff_row` scope of the decode
program's executions in the trace, as metrics/win_row_roofline_share.py
weighs its live slots: the requests' records', each at its mean length in
the traced slice and weighed by the share of the slice it was decoding
(the program steps every slot, live or not, so the share reads low while
slots are empty, never high). None where the trace, the scope or the
family's count is absent."""
from perfbench import metrics_lib as ml, scope_times, spec, yardstick
from perfbench.metrics.win_row_roofline_share import live_window

SCOPE, PROGRAM = "diff_row", "jit_decode"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "diff_row_bytes"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    live = ml.mean_live_tokens(run, *run["traced"])
    windows = live_window(run, *run["traced"], cfg["sliding_window"])
    if got is None or not live:
        return None
    seconds, runs = got
    floor_s = runs * family.diff_row_bytes(cfg, live, windows, 2.0) \
        / yardstick.peaks(run["device"]["kind"])["bytes_per_s"]
    return floor_s / seconds * 100.0
