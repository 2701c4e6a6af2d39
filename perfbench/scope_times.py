"""Device time of a named scope inside one jitted program, from the
profiler's trace.

`trace_reduce` names a device op by its HLO line, which carries no scope.
The trace's own metadata does: each op's `tf_op` is its JAX name with the
`jax.named_scope`s on its path (`jit(prefill)/.../lightning_scan/while/
body/dot_general`), and its `program_id` is the number in the module's
event name (`jit_prefill(<id>)`). Reading them takes the XSpace protobuf
itself, which JAX's reader does not expose; TensorFlow's generated module
for it is in the installation (about 20 s to import; a traced run without
it raises). A fusion is filed under its root op's scope. Where the trace
or the scope is absent: None. `trace_reduce` could carry `tf_op` and
`program_id` in its own reduction and this file go: PERF.md section 7, for
a `benchmark` PR (this one may not edit `trace_reduce`).
"""

from __future__ import annotations

import functools
import os

from perfbench import spec, trace_reduce


def _stat(plane, stat):
    which = stat.WhichOneof("value")
    if which == "ref_value":
        return plane.stat_metadata[stat.ref_value].name
    return getattr(stat, which) if which else None


def scope_seconds(run, scope: str, program: str):
    """(seconds of device time inside `scope` in executions of `program`,
    executions of `program`) over the traced slice, averaged over the
    device planes; None where there is nothing to read."""
    if not run.get("traced") or not run.get("cell"):
        return None
    path = trace_reduce.find_xplane(os.path.join(
        spec.ROOT, ".perfbench_out", "trace", run["cell"]))
    if path is None:
        return None
    space = _parsed(path)
    if space is None:
        return None
    seconds, runs, planes = 0.0, 0, 0
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        lines = {ln.name: ln for ln in plane.lines}
        mods = lines.get(trace_reduce.MODULE_LINES[0])
        ops = lines.get(trace_reduce.OP_LINES[0])
        if mods is None or ops is None:
            continue
        ids = set()
        for ev in mods.events:
            name = plane.event_metadata[ev.metadata_id].name
            if trace_reduce._program_name(name) == program:
                ids.add(name[len(program) + 1:-1])
                runs += 1
        inside = {}
        for mid, meta in plane.event_metadata.items():
            stats = {names.get(s.metadata_id): _stat(plane, s)
                     for s in meta.stats}
            inside[mid] = (str(stats.get("program_id")) in ids
                           and f"/{scope}/" in f"/{stats.get('tf_op')}/"
                           .replace(":", "/"))
        busy, _ = trace_reduce._union(
            (ev.offset_ps, ev.offset_ps + ev.duration_ps)
            for ev in ops.events if inside.get(ev.metadata_id))
        seconds += busy / 1e12
        planes += 1
    if not planes or not runs or seconds <= 0:
        return None
    return seconds / planes, runs // planes


@functools.lru_cache(maxsize=2)
def _parsed(path: str):
    """The trace as an XSpace, read once for the readers that share it."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:
        # a traced run whose scopes cannot be read says so: a silent None
        # would drop two roofline shares from the line unnoticed
        raise RuntimeError(
            "a --trace 1 run reads the scopes' device times with "
            "TensorFlow's xplane_pb2, which this installation lacks") from e
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def tile_tokens(run):
    """Mean real prompt tokens a prefill dispatch carried over the
    counters' window (delta prefill_tokens / delta prefill_dispatches)."""
    from perfbench import metrics_lib as ml
    n = ml.counter_delta(run, "prefill_dispatches")
    return ml.counter_delta(run, "prefill_tokens") / n if n else None
