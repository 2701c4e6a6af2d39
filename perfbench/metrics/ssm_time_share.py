"""Model step: the device time inside the `hyb_ssm` scope (a block's
state-space branch: its projections, convolution, recurrence, gated norm)
in the executions of both step programs in the traced slice, over the
device time of those executions, in per cent: what of a step the new
mechanism is. None where the trace or the scope is absent."""
from perfbench import metrics_lib as ml, scope_times

PROGRAMS = ("jit_prefill", "jit_decode")


def scope_time_share(run, scope):
    if not run.get("traced"):
        return None
    inside = [scope_times.scope_seconds(run, scope, p) for p in PROGRAMS]
    whole = sum(sum(ml.program_durations(run, p)) for p in PROGRAMS)
    if not whole or not any(inside):
        return None
    return sum(got[0] for got in inside if got) / whole * 100.0


def read(run):
    return scope_time_share(run, "hyb_ssm")
