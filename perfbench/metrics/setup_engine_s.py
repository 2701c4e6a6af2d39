"""Engine: the replica's `launch.engine` span (`startup_engine_s` of
`InferenceEngine.stats()` at the window's first instant): the engine's build,
the pools' allocation (`launch.engine.pools`) and the programs it runs
ahead (`launch.engine.programs`) its two parts. None where the program
records no such phase."""
from perfbench import setup_phases


def read(run):
    return setup_phases.engine_s(run)
