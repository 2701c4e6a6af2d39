"""The fixture root's `mistral` family is the benchmark's own: a root
finds a family under its own `paths`, as it finds everything else."""
from perfbench.families.mistral import *  # noqa: F401,F403
