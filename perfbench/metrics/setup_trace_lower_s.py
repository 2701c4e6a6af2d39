"""Model step: seconds the replica's process spent tracing programs and
lowering them to MLIR before the window (`xla_trace_s + xla_lower_s` of
`InferenceEngine.stats()` at the window's first instant, the compile
watch's totals): what a warm compile cache does not save. None where the
program keeps no such totals."""
from perfbench import setup_phases


def read(run):
    return setup_phases.trace_lower_s(run)
