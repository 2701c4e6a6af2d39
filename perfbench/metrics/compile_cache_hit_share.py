"""Device: of the backend compile requests of the replica's process before
the window, those the persistent compile cache answered, in per cent
(`xla_cache_hits / xla_compiles`; over the REQUESTS: JAX counts a miss only
where it writes an entry, and a program that compiles in under
`jax_persistent_cache_min_compile_time_secs` is compiled anew at every
start and counted as neither). Tells a warm start from a cold one, a
program that stopped being cacheable, and how many small programs every
start compiles. None where the program keeps no such totals or compiled
nothing."""
from perfbench import setup_phases


def read(run):
    return setup_phases.cache_hit_share(run)
