"""Kernels, serving: the least time the state-space layers' one-row step
could take on this chip (its bytes over the peak bandwidth: each LIVE
slot's float32 state of each layer in and out and the row's inputs, by the
family's `ssd_step_bytes`; its FLOPs are a thousandth of that time) over
the device time inside the `ssd_step` scope of the decode program's
executions in the trace. The live slots are the requests' records', each
weighed by the share of the traced slice it was decoding (the program
steps every slot, live or not, so the share reads low while slots are
empty, never high). None where the trace, the scope or the family's count
is absent."""
from perfbench import scope_times, spec, yardstick

SCOPE, PROGRAM = "ssd_step", "jit_decode"


def live_rows(run, a: float, b: float) -> float:
    """The time average over [a, b) of the requests between their first
    and last token."""
    total = 0.0
    for r in run["records"]:
        arr = r["arrivals"]
        if len(arr) >= 2:
            total += max(0.0, min(b, arr[-1]) - max(a, arr[0]))
    return total / (b - a)


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "ssd_step_bytes"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    rows = live_rows(run, *run["traced"])
    if got is None or not rows:
        return None
    seconds, runs = got
    floor_s = runs * family.ssd_step_bytes(cfg, rows, 2.0) \
        / yardstick.peaks(run["device"]["kind"])["bytes_per_s"]
    return floor_s / seconds * 100.0
