"""Model step: milliseconds a step in which a program was running on the
device and none of its ops was: the module executions' union less the ops'
union within it, over the whole `engine.step` spans of the traced slice
(perfbench/host_spans.py). None where the trace holds no `engine.step`."""
from perfbench import host_spans


def read(run):
    return host_spans.idle_ms(run, "in_program")
