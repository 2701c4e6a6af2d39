"""Kernels, serving, where the family's decode step reads of a slot what
depends on THAT slot's length (a selection's cap, a state whatever the
length): the least time one decode step could take on this chip over the
decode program's median device time, as metrics/decode_roofline_share.py,
but the floor's bytes summed a request: each request live in the traced
slice, at its own mean length there, weighs by the share of the slice it
was live (the family's `decode_step_bytes` of that one slot, less what a
step moves with no slot live)."""
from perfbench import metrics_lib as ml, spec, yardstick

PROGRAM = "jit_decode"
DTYPE_BYTES = {"bfloat16": 2.0, "float32": 4.0}


def live_slots(run, a: float, b: float):
    """[(share of [a, b) a request was live, its mean length there)],
    reckoned as ml.mean_live_tokens reckons its sum."""
    out = []
    for r in run["records"]:
        arr = r["arrivals"]
        if len(arr) < 2:
            continue
        lo, hi = max(a, arr[0]), min(b, arr[-1])
        if hi <= lo:
            continue
        grown = ((lo + hi) / 2.0 - arr[0]) / (arr[-1] - arr[0]) * len(arr)
        out.append(((hi - lo) / (b - a), r["prompt_len"] + grown))
    return out


def read(run):
    d = ml.program_durations(run, PROGRAM)
    if not d or not run.get("traced"):
        return None
    cfg = run["config"]
    pb = DTYPE_BYTES[cfg["param_dtype"]]
    step_bytes = spec.family_of(cfg).decode_step_bytes
    idle = step_bytes(cfg, [], pb, 2.0)
    nbytes = idle + sum(
        share * (step_bytes(cfg, [length], pb, 2.0) - idle)
        for share, length in live_slots(run, *run["traced"]))
    floor_s = nbytes / yardstick.peaks(run["device"]["kind"])["bytes_per_s"]
    return floor_s / yardstick.median(d) * 100.0
