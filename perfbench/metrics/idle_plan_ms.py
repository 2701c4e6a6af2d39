"""Engine: milliseconds a step in which no program ran on the device while
the engine's thread was planning
(reap, evictions, the prefill plan),
over the whole `engine.step` spans of the traced slice
(perfbench/host_spans.py: the stretches between module executions, split
among the `engine.plan` spans they overlap). None where the trace holds no
`engine.step`."""
from perfbench import host_spans


def read(run):
    return host_spans.idle_ms(run, "plan")
