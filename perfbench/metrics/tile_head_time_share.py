"""Model step: the device time inside the `lm_head` scope (the cached
forward's final norm and unembedding) in the executions of the tile
program, `jit_prefill`, over that program's device time in the traced
slice, in per cent: what of a tile step the head is, now that it runs
over the rows the step samples. The tile program alone: `jit_decode`
computes what it did before the scope had a name, and a warm compilation
cache serves it without one (metrics/ssm_time_share.py reads both
programs because both were new with their scopes). None where the trace
or the scope is absent."""
from perfbench import metrics_lib as ml, scope_times

PROGRAM = "jit_prefill"


def read(run):
    if not run.get("traced"):
        return None
    inside = scope_times.scope_seconds(run, "lm_head", PROGRAM)
    whole = sum(ml.program_durations(run, PROGRAM))
    if not inside or not whole:
        return None
    return inside[0] / whole * 100.0
