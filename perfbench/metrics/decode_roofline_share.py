"""Kernels, serving: the least time one decode step could take on this
chip (the bytes it must read, weights as stored plus the live K and V at
the traced slice's mean live lengths, over the peak bandwidth; decode is
bandwidth-bound) over the decode program's median device time."""
from perfbench import metrics_lib as ml, spec, yardstick

PROGRAM = "jit_decode"
DTYPE_BYTES = {"bfloat16": 2.0, "float32": 4.0}


def read(run):
    d = ml.program_durations(run, PROGRAM)
    if not d or not run.get("traced"):
        return None
    cfg = run["config"]
    live = ml.mean_live_tokens(run, *run["traced"])
    nbytes = spec.family_of(cfg).decode_step_bytes(
        cfg, [live], DTYPE_BYTES[cfg["param_dtype"]], 2.0)
    floor_s = nbytes / yardstick.peaks(run["device"]["kind"])["bytes_per_s"]
    return floor_s / yardstick.median(d) * 100.0
