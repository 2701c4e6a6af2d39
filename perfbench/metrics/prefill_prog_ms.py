"""Model step: median device duration of the prefill-chunk program's
executions in the trace (the jitted `prefill` of inference/engine.py)."""
from perfbench import metrics_lib as ml, yardstick

PROGRAM = "jit_prefill"


def read(run):
    d = ml.program_durations(run, PROGRAM)
    return yardstick.median(d) * 1e3 if d else None
