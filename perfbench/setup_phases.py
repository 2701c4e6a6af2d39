"""A serving cell's set-up, told by phase: what the readers of the
`setup_*` metrics and of `compile_cache_hit_share` share.

The replica says how it started through `InferenceEngine.stats()`, which
`replica.bench_counters` carries to the harness at the window's first
instant (`run["counters"]["t0"]`): `startup_t_mono` (time.monotonic() where
the worker entered the replica's `__init__`, its `launch.callable_init`
span), `startup_weights_s` and `startup_engine_s` (the `launch.weights` and
`launch.engine` spans) and the compile watch's totals, cumulative for the
process, so at `t0` they are the set-up's (`xla_trace_s`, `xla_lower_s`,
`xla_compile_s`, `xla_compiles`, `xla_cache_hits`). Both processes are on
one host and time.monotonic() is one clock, so the harness's own stamps
(`T_START`, which is `t_win0 - setup_s`, and `t_win0`) meet the replica's.

`launch_s + weights_s + engine_s + rest_s + ramp_s` is `setup_s` by
construction: the first four are what the program says it was doing, the
ramp is the mix's, and `rest_s` is what is left (the rest of `__init__`,
the replica made ready, the handle, the two warm-up requests). The compile
stages are a second cut of the same seconds. Every function gives None,
never 0, on a program without the counters or with nothing to divide.
"""

from __future__ import annotations

# serve_cell.run_window opens the window at `now + ramp_s + 0.25`
WINDOW_LEAD_S = 0.25


def _at_window_start(run) -> dict:
    return (run.get("counters") or {}).get("t0") or {}


def ramp_s(run):
    return float(run["mix"].get("ramp_s", 0.0)) + WINDOW_LEAD_S


def launch_s(run):
    t_init = _at_window_start(run).get("startup_t_mono")
    if t_init is None:
        return None
    return t_init - (run["t_win0"] - run["setup_s"])


def weights_s(run):
    return _at_window_start(run).get("startup_weights_s")


def engine_s(run):
    return _at_window_start(run).get("startup_engine_s")


def rest_s(run):
    told = (launch_s(run), weights_s(run), engine_s(run))
    if None in told:
        return None
    return run["setup_s"] - sum(told) - ramp_s(run)


def trace_lower_s(run):
    c = _at_window_start(run)
    if "xla_trace_s" not in c or "xla_lower_s" not in c:
        return None
    return c["xla_trace_s"] + c["xla_lower_s"]


def compile_s(run):
    return _at_window_start(run).get("xla_compile_s")


def cache_hit_share(run):
    c = _at_window_start(run)
    if not c.get("xla_compiles") or "xla_cache_hits" not in c:
        return None
    return c["xla_cache_hits"] / c["xla_compiles"] * 100.0
