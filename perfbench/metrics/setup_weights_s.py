"""Serve plane: the replica's `launch.weights` span (`startup_weights_s` of
`InferenceEngine.stats()` at the window's first instant): the weights made
or attached, until the call that makes them returns. None where the program
records no such phase."""
from perfbench import setup_phases


def read(run):
    return setup_phases.weights_s(run)
