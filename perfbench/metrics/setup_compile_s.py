"""Device: seconds the replica's process spent in backend compiles and in
loads from the persistent compile cache before the window (`xla_compile_s`
of `InferenceEngine.stats()` at the window's first instant). None where the
program keeps no such total."""
from perfbench import setup_phases


def read(run):
    return setup_phases.compile_s(run)
