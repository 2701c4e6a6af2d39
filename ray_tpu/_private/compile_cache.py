"""Where JAX keeps compiled programs between processes and runs.

One rule for every process that compiles (workers, chip_smoke.py,
perfbench/): if ``JAX_COMPILATION_CACHE_DIR`` is set, that
directory is used and no other is set in code; otherwise the cache lives
at ``<checkout>/.jax_cache``, derived from where this package sits —
never a temp name, a pid or a time, because the path is part of the
cache key and a directory that moves never hits. The choice is written
back to the environment, so every process started from here (raylet,
workers, a benchmark's children) uses the same directory.

The compile watch (`watch()`) is in this module too, since every process
that compiles passes through it: `jax.monitoring` listeners that put each
of JAX's three compile stages on the flight recorder (`xla.trace`,
`xla.lower`, `xla.compile`, category `compile`, under the calling
thread's trace context, on JAX's own start and end) and keep running
totals beside the ring (`totals()`), which a long run does not rotate
away. No knob: the listeners run only when JAX compiles (a jitted call
that hits the C++ fast path calls none of them) and the recorder's own
switch covers the spans. Nothing here imports JAX: a process that has
not imported it (the benchmark's load generator) gets no listener.
"""

from __future__ import annotations

import collections
import os
import sys
import threading

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Call before the first compile. Returns the directory in use."""
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        os.environ[CACHE_DIR_ENV] = path
        jax = sys.modules.get("jax")
        if jax is not None:
            # imported before we ran: the env var was read already
            jax.config.update("jax_compilation_cache_dir", path)
    watch()
    return path


def cache_entries() -> int:
    """How many compiled programs the directory holds right now."""
    try:
        return sum(1 for name in os.listdir(configure_compile_cache())
                   if name.endswith("-cache"))
    except FileNotFoundError:
        return 0


# ------------------------------------------------------------ compile watch
# JAX's names (jax/_src/dispatch.py, compiler.py, compilation_cache.py)
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
# what the cache says of a compile, before the compile's own span ends
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "request",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "stored",   # an entry WRITTEN
}
_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_MAX_PENDING = 65536    # traces a thread may hold unlowered (an unrolled
                        # model's inner ones, until the outer one ends)

_totals = {"compiles": 0, "cache_hits": 0, "cache_misses": 0,
           "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
           "cache_load_s": 0.0}
_lock = threading.Lock()
_tls = threading.local()
_watching = False


def watch() -> bool:
    """Install the compile watch, once a process, where JAX is imported
    already (a worker configures its cache BEFORE it imports JAX, so the
    program's first users of JAX call this too: `LLMDeployment`,
    `make_train_fns`, the train worker's set-up). Returns whether the
    process is watched."""
    global _watching
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    with _lock:
        if _watching:
            return True
        _watching = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_time_span_listener(_on_span)
    return True


def totals() -> dict:
    """The process's running totals: `compiles` (backend compile
    requests), `cache_hits` (those the persistent cache answered),
    `cache_misses` (JAX's own count: entries it WROTE; a program that
    compiles in under `jax_persistent_cache_min_compile_time_secs` is
    compiled anew at every start and is neither), and the seconds of
    each stage; `compile_s` holds the cache's loads (`cache_load_s`)."""
    with _lock:
        return dict(_totals)


def _add(**deltas) -> None:
    with _lock:
        for key, value in deltas.items():
            _totals[key] += value


def _fun(name: str) -> str:
    """`jit(prefill)`, the lowering's and the compile's name of a
    program, as the trace names it: `prefill`."""
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _record(name: str, start: float, end: float, **attrs) -> None:
    from ray_tpu._private import events
    events.record_complete(name, start, end, category="compile", **attrs)


def _lowered(start: float) -> None:
    """A program's lowering began at `start`: record its trace. The
    thread's outermost traces since its last lowering are the program's
    own, the last of them, and before it what was traced and never
    lowered (a hit of JAX's trace cache, 8 us; an `eval_shape`): ONE
    `xla.trace`, the program's, which says what the others took
    (`unlowered_s`), and all of them in `trace_s`. A trace that began
    after `start` ran INSIDE the lowering (a kernel's body is traced
    there): `lower_s` has it."""
    traces, _tls.traces = getattr(_tls, "traces", None), None
    while traces and traces[-1][0] >= start:
        traces.pop()
    if not traces:
        return
    total = sum(end - begin for begin, end, _ in traces)
    begin, end, fun = traces[-1]
    _add(trace_s=total)
    attrs = {"fun": fun}
    if len(traces) > 1:
        attrs["unlowered_s"] = round(total - (end - begin), 6)
    _record("xla.trace", begin, end, **attrs)


def _on_span(event: str, start: float, end: float, fun_name: str = "",
             **_kw) -> None:
    if event == _TRACE:
        # JAX reports every inner `jit` it meets while tracing an outer
        # one, each at its END, the inner ones first (an unrolled model:
        # thousands). A trace swallows those inside it, which are the
        # last that came, so a thread holds its OUTERMOST traces until
        # the program's lowering (the next thing JAX does) records
        # them: nothing nested is counted twice and the ring is not
        # flooded
        traces = getattr(_tls, "traces", None)
        if traces is None:
            traces = _tls.traces = collections.deque()
        while traces and traces[-1][0] >= start:
            traces.pop()
        traces.append((start, end, fun_name))
        if len(traces) > _MAX_PENDING:      # a thread that never lowers
            oldest = traces.popleft()
            _add(trace_s=oldest[1] - oldest[0])
    elif event == _LOWER:
        _lowered(start)
        _add(lower_s=end - start)
        _record("xla.lower", start, end, fun=_fun(fun_name))
    elif event == _COMPILE:
        # the cache's events of this compile came first, on this thread
        cache, _tls.cache = getattr(_tls, "cache", None) or {}, {}
        hit, stored = bool(cache.get("hit")), bool(cache.get("stored"))
        load_s = cache.get("load_s", 0.0) if hit else 0.0
        attrs = {"fun": _fun(fun_name)}
        if hit:
            attrs.update(cache="hit", load_s=round(load_s, 6))
        elif cache.get("request") and sys.modules[
                "jax"].config.jax_compilation_cache_dir:
            attrs.update(cache="miss", stored=stored)
        else:           # JAX counts a request with no directory, too
            attrs["cache"] = "off"
        _add(compiles=1, compile_s=end - start, cache_hits=int(hit),
             cache_misses=int(stored), cache_load_s=load_s)
        _record("xla.compile", start, end, **attrs)


def _pending_cache() -> dict:
    cache = getattr(_tls, "cache", None)
    if cache is None:
        cache = _tls.cache = {}
    return cache


def _on_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        _pending_cache()[key] = True


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _LOAD:
        _pending_cache()["load_s"] = seconds
