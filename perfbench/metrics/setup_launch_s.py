"""Runtime: process start to the worker entering the replica's `__init__`
(`startup_t_mono` of `InferenceEngine.stats()`, the `t_mono` of the
`launch.callable_init` span, less the harness's `T_START`): the harness's
imports, `ray_tpu.init`, `serve.run`, the controller, placement, the
worker's spawn and imports, `become_actor`; the `actor.launch` chain splits
it on the timeline. None where the program says no such instant."""
from perfbench import setup_phases


def read(run):
    return setup_phases.launch_s(run)
