"""Kernels, serving: the least time the sliding-window layers' decode rows
could take on this chip (each LIVE slot's min(length, window) positions of
K and V of each sliding layer over the peak bandwidth, by the family's
`win_row_bytes`; its FLOPs are a hundredth of that time) over the device
time inside the `win_row` scope of the decode program's executions in the
trace. The live slots are the requests' records', each at its mean length
in the traced slice and weighed by the share of the slice it was decoding
(the program steps every slot, live or not, so the share reads low while
slots are empty, never high). None where the trace, the scope or the
family's count is absent."""
from perfbench import scope_times, spec, yardstick

SCOPE, PROGRAM = "win_row", "jit_decode"


def live_window(run, a: float, b: float, window: int) -> float:
    """The time average over [a, b) of the sum over the requests between
    their first and last token of min(length, window)."""
    total = 0.0
    for r in run["records"]:
        arr = r["arrivals"]
        if len(arr) < 2:
            continue
        lo, hi = max(a, arr[0]), min(b, arr[-1])
        if hi <= lo:
            continue
        grown = ((lo + hi) / 2.0 - arr[0]) / (arr[-1] - arr[0]) * len(arr)
        total += (hi - lo) * min(r["prompt_len"] + grown, window)
    return total / (b - a)


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "win_row_bytes"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    live = live_window(run, *run["traced"], cfg["sliding_window"])
    if got is None or not live:
        return None
    seconds, runs = got
    floor_s = runs * family.win_row_bytes(cfg, live, 2.0) \
        / yardstick.peaks(run["device"]["kind"])["bytes_per_s"]
    return floor_s / seconds * 100.0
