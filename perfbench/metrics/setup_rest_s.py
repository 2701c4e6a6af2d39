"""Serve plane: `setup_s` less `setup_launch_s`, `setup_weights_s`,
`setup_engine_s` and the ramp (the mix's `ramp_s` + 0.25 s): the rest of the
replica's `__init__`, the replica made ready, the handle, the two warm-up
requests and what they compile. None where one of the three is."""
from perfbench import setup_phases


def read(run):
    return setup_phases.rest_s(run)
