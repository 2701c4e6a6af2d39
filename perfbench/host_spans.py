"""The device's idle time told by what the engine's thread was doing.

The engine marks its step on the profiler's own clock (`engine.step`, and
under it `engine.plan`, `engine.dispatch`, `engine.read`, `engine.emit`:
`jax.profiler.TraceAnnotation`s on the engine's thread, ray_tpu/inference/
engine.py `_StepPhases`), so a host plane of the trace holds them beside
the device planes' "XLA Modules" (one event an execution of a program) and
"XLA Ops". Over the `engine.step` spans that lie whole inside the trace
(first one's start to last one's end; a step the trace's edge cut leaves
its phases without their parent, and they are left out) this gives, a
step:

(a) the time BETWEEN programs, the complement of the union of module
    executions, each stretch split among the phase spans it overlaps; what
    lies under no phase is `outside` (between two steps: the loop's wait
    for work, the lock, another thread holding the GIL; and the seams of
    under a microsecond between a step's phases);
(b) the idle time INSIDE programs: the module executions' union less the
    ops' union within it.

The six add up to the range less the ops' union. A step is every
`engine.step`, as `engine_step_ms` counts every call of `step()`. Read
with JAX's own `ProfileData` alone, in any process (no backend is opened).
Where the trace holds no `engine.step` (a program without the annotations,
a training cell) every reader returns None.

**The two clocks.** A device plane's timestamps and the host planes' are
not one clock: on the v5e the device's read 0.4-1.6 ms EARLY, another value
every profiling session (in one slice every program "began" 1.4 ms before
the host had enqueued it and "ended" 1.8 ms before the host heard of it;
PERF.md section 6, PR 42): up to half of the 3 ms a step that is to be
attributed. The runtime's own host events bound the difference from both
sides: a program cannot begin before its `DoEnqueueProgram` began, nor end
after its `tpu::System::Execute=>Done`; over a slice's hundreds of programs
the largest of the first differences and the least of the second lie
0.25-0.4 ms apart, and the device plane is shifted by their middle
(`device_clock` in the result says by how much, and between what). Where
the trace lacks those events, or holds several device planes with programs
(the events do not say whose program they are), nothing is shifted.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import statistics
import sys

from perfbench import spec, trace_reduce

PHASES = ("plan", "dispatch", "read", "emit")
KINDS = PHASES + ("outside", "in_program")
STEP_PROGRAMS = ("jit_decode", "jit_prefill")
ENQUEUE, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"


def _merged(intervals):
    """Sorted disjoint [start, end) intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clipped(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap(a, b):
    """Total length of the intersection of two sorted disjoint lists."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def _split(stretch, phases):
    """The part of `stretch` (start, end) under each of the sorted,
    disjoint `phases` [(start, end, name)], the rest as `outside`."""
    s, e = stretch
    by = dict.fromkeys(PHASES + ("outside",), 0.0)
    for ps, pe, name in phases:
        if pe <= s:
            continue
        if ps >= e:
            break
        by[name] += min(e, pe) - max(s, ps)
    by["outside"] = (e - s) - sum(by[p] for p in PHASES)
    return by


def _engine_lines(planes):
    """The host lines that carry `engine.step`: a line an engine's thread."""
    for pname, lines in planes:
        if pname.startswith("/device:"):
            continue
        for _, events in lines:
            if any(ev[2] == "engine.step" for ev in events):
                yield [ev for ev in events if ev[2].startswith("engine.")]


def _in_order(host, device):
    """`host` times less `device` times, each sorted and one an execution
    in the same order. The trace's edges may cut a few off either end, so
    the pairing slides by up to four places, to where the differences are
    least (their median nearest zero): the clocks differ by far less than
    two programs lie apart."""
    best = None
    for shift in range(-4, 5):
        d = [h - device[i + shift] for i, h in enumerate(host)
             if 0 <= i + shift < len(device)]
        if len(d) >= 8 and (best is None or abs(statistics.median(d))
                            < abs(statistics.median(best))):
            best = d
    return best


def _device_clock(planes, mods):
    """Nanoseconds to add to a device plane's times to lay them on the
    host planes' clock, with the bounds it was taken between; None where
    the runtime's events are not in the trace. At least: every program is
    enqueued once and in order, so enqueues and executions pair by order,
    and none began before its enqueue did. At most: a step program's
    `Done` is the first at or after its end (its end by the lower bound:
    the programs before it ended a step program's length earlier, and a
    `Done` that is missing only loosens the bound), and none ended after
    it."""
    marks = {ENQUEUE: [], DONE: []}
    for pname, lines in planes:
        if not pname.startswith("/device:"):
            for _, events in lines:
                for ev in events:
                    if ev[2] in marks:
                        marks[ev[2]].append(ev[0])
    began = _in_order(sorted(marks[ENQUEUE]), sorted(e[0] for e in mods))
    done = sorted(marks[DONE])
    if not began or not done:
        return None
    lo = max(began)
    ended = []
    for e in mods:
        if trace_reduce._program_name(e[2]) in STEP_PROGRAMS:
            i = bisect.bisect_left(done, e[0] + e[1] + lo)
            if i < len(done):
                ended.append(done[i] - (e[0] + e[1]))
    if not ended:
        return None
    return {"shift_ns": (lo + min(ended)) / 2.0, "at_least_ns": lo,
            "at_most_ns": min(ended), "programs": len(ended)}


def _whole_steps(events):
    """(steps, phases): the `engine.step` spans as (start, end, stats) and
    the phase spans that lie inside one, as (start, end, name), sorted."""
    steps = sorted(((s, s + d, dict(stats or ()))
                    for s, d, name, stats in events
                    if name == "engine.step"), key=lambda x: x[0])
    phases, i = [], 0
    for s, d, name, _ in sorted(events, key=lambda x: x[0]):
        kind = name[len("engine."):]
        if kind not in PHASES:
            continue
        while i < len(steps) and steps[i][1] < s + d:
            i += 1
        if i < len(steps) and steps[i][0] <= s:
            phases.append((s, s + d, kind))
    return steps, phases


def _program_lags(steps, phases, programs):
    """Each execution of a step program against the `engine.dispatch` span
    that issued it. One device stream and one step program a dispatch
    span, so the executions that begin inside the whole steps and the
    dispatch spans pair in order; from the last backwards an execution
    takes the latest span still free that began before it did. What is
    left over: `unissued`, executions no span is left for (a program the
    cut step before the range issued), and `unrun`, spans whose program
    began after the range. `late`: an execution that begins after the end
    of the `engine.read` that follows its span in the step (a tile nobody
    reads: after the end of the next step). Clocks that disagree show as
    pairs a step apart: every one late."""
    spans = [(s, e) for s, e, k in phases if k == "dispatch"]
    runs = sorted(s for s in programs if steps[0][0] <= s <= steps[-1][1])
    if not spans or not runs:
        return None
    reads = [(s, e) for s, e, k in phases if k == "read"]
    lags, late, unissued, i = [], 0, 0, len(spans) - 1
    for run in reversed(runs):
        while i >= 0 and spans[i][0] > run:
            i -= 1
        if i < 0:
            unissued += 1
            continue
        ds, de = spans[i]
        n = next(n for n, st in enumerate(steps) if st[1] >= de)
        limit = next((e for s, e in reads if s >= de and e <= steps[n][1]),
                     steps[min(n + 1, len(steps) - 1)][1])
        lags.append(run - ds)
        late += run > limit
        i -= 1
    if not lags:
        return None
    return {"programs": len(lags), "unissued": unissued,
            "unrun": len(spans) - len(lags), "late": late,
            "lag_ms_median": statistics.median(lags) / 1e6,
            "lag_ms_max": max(lags) / 1e6}


def reduce_planes(planes, window=None):
    """`planes`: iterable of (plane_name, [(line_name, [(start_ns, dur_ns,
    name, stats)])]), `stats` the pairs of an annotation's keyword
    arguments (only `engine.step`'s are read). Milliseconds a step out,
    averaged over the device planes; None where no engine's thread or no
    device plane is in the trace. `window`: the traced slice as the
    harness clocked it, (t_a, t_b) of time.monotonic(); laid on the trace
    by the steps' anchors it gives `window`, the slice's own idle time
    (the run's `window_s - busy_s` takes `busy_s` over the whole trace,
    which begins before `start_trace` returns and ends after `t_b`)."""
    planes = list(planes)
    lines = list(_engine_lines(planes))
    if not lines:
        return None
    steps, phases = _whole_steps(lines[0])
    if not steps:
        return None
    lo, hi, n = steps[0][0], steps[-1][1], len(steps)
    anchors = [a - st["t_mono"] * 1e9 for a, _, st in steps
               if "t_mono" in st]
    offset = statistics.median(anchors) if anchors else None
    devices, stretches, lags, slices, clock = [], [], None, [], None
    n_planes = sum(pname.startswith("/device:") and any(
        events for ln, events in plane_lines
        if ln in trace_reduce.MODULE_LINES) for pname, plane_lines in planes)
    for pname, plane_lines in planes:
        if not pname.startswith("/device:"):
            continue
        by_line = dict(plane_lines)
        mods = [e for ln in trace_reduce.MODULE_LINES
                for e in by_line.get(ln, [])]
        ops = [e for ln in trace_reduce.OP_LINES for e in by_line.get(ln, [])]
        if not mods:
            continue
        if n_planes == 1:
            clock = _device_clock(planes, mods)
        add = clock["shift_ns"] if clock else 0.0
        mods = [(e[0] + add,) + tuple(e[1:]) for e in mods]
        running = _merged(_clipped(((e[0], e[0] + e[1]) for e in mods),
                                   lo, hi))
        busy_all = _merged((e[0] + add, e[0] + e[1] + add) for e in ops)
        busy = _clipped(busy_all, lo, hi)
        if window is not None and offset is not None:
            a, b = (t * 1e9 + offset for t in window)
            inside = sum(e - s for s, e in _clipped(busy_all, a, b))
            slices.append({"idle_s": (b - a - inside) / 1e9,
                           "busy_outside_s": (sum(e - s for s, e in busy_all)
                                              - inside) / 1e9})
        idle = dict.fromkeys(KINDS, 0.0)
        edges = [lo] + [t for iv in running for t in iv] + [hi]
        for stretch in zip(edges[::2], edges[1::2]):
            if stretch[1] <= stretch[0]:
                continue
            by = _split(stretch, phases)
            for k, v in by.items():
                idle[k] += v
            stretches.append((stretch[1] - stretch[0], by))
        idle["in_program"] = sum(e - s for s, e in running) \
            - _overlap(running, busy)
        devices.append(idle)
        if lags is None:
            lags = _program_lags(steps, phases, [
                e[0] for e in mods
                if trace_reduce._program_name(e[2]) in STEP_PROGRAMS])
    if not devices:
        return None
    covered = [sum(e - s for s, e, _ in phases if s >= a and e <= b)
               / (b - a) for a, b, _ in steps if b > a]
    stretches.sort(key=lambda x: -x[0])
    return {
        "steps": n, "devices": len(devices), "range_s": (hi - lo) / 1e9,
        "step_ms_mean": sum(b - a for a, b, _ in steps) / n / 1e6,
        "idle_ms_per_step": {
            k: sum(d[k] for d in devices) / len(devices) / n / 1e6
            for k in KINDS},
        "phase_cover_min": min(covered) if covered else None,
        "phase_cover_mean": sum(covered) / len(covered) if covered else None,
        # trace nanoseconds less time.monotonic() nanoseconds, by the
        # steps' anchors: one number if the clocks run alike
        "mono_to_trace_ns": offset,
        "device_clock": clock,
        "mono_to_trace_spread_ns": max(anchors) - min(anchors)
        if anchors else None,
        "window": {k: sum(d[k] for d in slices) / len(slices)
                   for k in slices[0]} if slices else None,
        "dispatch_to_program": lags,
        "longest_between_programs": [
            [length / 1e9, max(by, key=by.get),
             {k: v / 1e9 for k, v in by.items() if v > 0}]
            for length, by in stretches[:10]],
    }


def read_xplane(path: str):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (trace_reduce.MODULE_LINES
                                            + trace_reduce.OP_LINES):
                continue
            lines.append((line.name, [
                (ev.start_ns, ev.duration_ns, ev.name,
                 list(ev.stats) if ev.name == "engine.step" else None)
                for ev in line.events
                if device or ev.name.startswith("engine.")
                or ev.name in (ENQUEUE, DONE)]))
        yield plane.name, lines


@functools.lru_cache(maxsize=2)
def _of_trace(trace_dir: str, window):
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return None
    found = reduce_planes(read_xplane(path), window)
    if found is None:
        return None
    with open(os.path.join(trace_dir, "host_spans.json"), "w") as f:
        json.dump(found, f)             # beside reduced.json
    idle = found["idle_ms_per_step"]
    print(f"host spans: {found['steps']} whole steps of "
          f"{found['step_ms_mean']:.3f} ms in {found['range_s']:.3f} s; "
          f"idle ms a step {json.dumps({k: round(v, 4) for k, v in idle.items()})}"
          f" = {sum(idle.values()):.4f}; phases cover "
          f"{found['phase_cover_min']:.4f} of a step at least; "
          f"dispatch to program {found['dispatch_to_program']}; "
          f"the slice by the anchors {found['window']}; "
          f"monotonic to trace spread {found['mono_to_trace_spread_ns']} ns; "
          f"device clock {found['device_clock']}",
          file=sys.stderr)
    for length, phase, by in found["longest_between_programs"]:
        print(f"host spans: between programs {length * 1e3:.3f} ms, "
              f"mostly {phase}: "
              f"{ {k: round(v * 1e3, 3) for k, v in by.items()} }",
              file=sys.stderr)
    return found


def of_run(run):
    """What `reduce_planes` finds in a traced run's trace, read once a
    process (and written to `host_spans.json` beside `reduced.json`, the
    ten longest stretches between programs to stderr); None where the run
    was not traced or its trace holds no `engine.step`."""
    if not run.get("traced") or not run.get("cell"):
        return None
    return _of_trace(os.path.join(spec.ROOT, ".perfbench_out", "trace",
                                  run["cell"]), tuple(run["traced"]))


def idle_ms(run, kind: str):
    """Idle milliseconds a step of the device put down to `kind` (a phase,
    `outside` or `in_program`)."""
    found = of_run(run)
    return None if found is None else found["idle_ms_per_step"][kind]
