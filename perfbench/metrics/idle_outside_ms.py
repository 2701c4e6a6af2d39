"""Engine: milliseconds a step in which no program ran on the device and
the engine's thread was in none of a step's phases: between two steps (the
loop's wait for work, the lock, another thread holding the GIL) and the
seams between a step's phases (perfbench/host_spans.py). None where the
trace holds no `engine.step`."""
from perfbench import host_spans


def read(run):
    return host_spans.idle_ms(run, "outside")
