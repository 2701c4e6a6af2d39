"""From the profiler's trace to numbers: device busy and idle, device
time per XLA program and per op, the longest idle gaps.

`start`/`stop` run in the process that holds the chip. `reduce_dir` reads
the `.xplane.pb` the profiler wrote, with nothing but JAX's own reader,
and can run in any process (it opens no backend). A device plane is one
whose name starts with `/device:`; on it the line "XLA Modules" holds one
event per execution of a jitted program and the line "XLA Ops" one event
per HLO op, the events of a `while` (a scan over layers) nested under it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


def start(log_dir: str) -> None:
    import jax
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # no Python call stacks: they slow
    opts.host_tracer_level = 1       # the host the trace is taken of
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find_xplane(log_dir: str):
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _program_name(event_name: str) -> str:
    """'jit_decode(1234567)' -> 'jit_decode'."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """An op's event carries its whole HLO line; keep its name and the
    shape it produces: '%fusion.7 = bf16[16,4096]{...} fusion(...)' ->
    'fusion.7 bf16[16,4096]'."""
    m = re.match(r"%?(\S+) = \(?(\w+\[[\d,]*\])?", event_name)
    if not m:
        return event_name[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def _union(intervals):
    """Total length and the gaps of a set of [start, end) intervals."""
    busy, gaps, end = 0.0, [], None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                gaps.append((end, s))
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy, gaps


def _self_times(events):
    """Per event (start, dur, name), its duration less its children's:
    events on one line nest (a `while` spans its body's ops)."""
    out = defaultdict(float)
    stack = []                                   # (end, name)
    for s, d, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= d
        out[name] += d
        stack.append((s + d, name))
    return out


def reduce_planes(planes) -> dict:
    """`planes`: iterable of (plane_name, [(line_name, [(start_ns, dur_ns,
    name)])]). Seconds out. Averaged over the device planes."""
    per_device = []
    for pname, lines in planes:
        if not pname.startswith("/device:"):
            continue
        lines = dict(lines)
        ops = [e for ln in OP_LINES for e in lines.get(ln, [])]
        mods = [e for ln in MODULE_LINES for e in lines.get(ln, [])]
        if not ops and not mods:
            continue
        per_device.append((ops, mods))
    if not per_device:
        return {}
    n = len(per_device)
    busy = 0.0
    programs = defaultdict(list)
    op_self = defaultdict(float)
    gaps = []
    t_first, t_last = None, None
    for ops, mods in per_device:
        spans = [(s, s + d) for s, d, _ in (ops or mods)]
        b, g = _union(spans)
        busy += b / 1e9 / n
        gaps.extend((e - s) / 1e9 for s, e in g)
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
        t_first = lo if t_first is None else min(t_first, lo)
        t_last = hi if t_last is None else max(t_last, hi)
        for s, d, name in mods:
            programs[_program_name(name)].append(d / 1e9)
        for name, d in _self_times(ops).items():
            op_self[op_name(name)] += d / 1e9 / n
    gaps.sort(reverse=True)
    return {
        "devices": n,
        "busy_s": busy,
        "first_to_last_op_s": (t_last - t_first) / 1e9,
        "programs": {k: {"count": len(v) // n, "total_s": sum(v) / n,
                         "durations_s": v}
                     for k, v in programs.items()},
        "op_self_s": dict(op_self),
        "idle_gaps_s": gaps[:200],
        "n_idle_gaps": len(gaps),
    }


def read_xplane(path: str):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        yield plane.name, [
            (line.name, [(ev.start_ns, ev.duration_ns, ev.name)
                         for ev in line.events])
            for line in plane.lines]


def reduce_dir(log_dir: str) -> dict:
    path = find_xplane(log_dir)
    if path is None:
        return {}
    return reduce_planes(read_xplane(path))


def breakdown(reduced: dict, top: int = 10):
    """The contract's `breakdown` (the device ops with the most self time,
    and the longest idle gaps), and beside it the XLA programs with the
    most device time as [name, seconds, executions]. No host span is in
    the trace yet, so a gap is named by its rank only (PERF.md, list for
    `tracing`)."""
    ops = sorted(reduced.get("op_self_s", {}).items(),
                 key=lambda kv: -kv[1])[:top]
    gaps = reduced.get("idle_gaps_s", [])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[f"gap_{i + 1}_of_{reduced.get('n_idle_gaps')}",
                           g] for i, g in enumerate(gaps)]}, sorted(
        ([k, v["total_s"], v["count"]]
         for k, v in reduced.get("programs", {}).items()),
        key=lambda r: -r[1])[:top]
