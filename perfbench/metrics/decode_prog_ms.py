"""Model step: median device duration of the decode program's executions
in the trace (the jitted `decode` of inference/engine.py)."""
from perfbench import metrics_lib as ml, yardstick

PROGRAM = "jit_decode"


def read(run):
    d = ml.program_durations(run, PROGRAM)
    return yardstick.median(d) * 1e3 if d else None
