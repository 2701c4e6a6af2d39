"""perfbench/host_spans.py on made-up planes, and the readers that PR 42
added beside it: new files and new entries only."""
import os

import pytest

from perfbench import host_spans as hs, spec

MS = 1_000_000
BENCH = spec.load_benchmark()
SERVING = ["mistral-7b.chat-steady", "mixtral-8x7b.batch-longprompt",
           "keye-vl-2.0-30b-a3b.longdoc-mixed", "minicpm-sala.longdoc-pool"]
NEW = {**{f"idle_{k}_ms": SERVING for k in hs.KINDS},
       "queue_wait_ms": SERVING, "prefill_span_ms": SERVING,
       "fused_step_share": SERVING,
       "dsa_streamed_per_live": ["keye-vl-2.0-30b-a3b.longdoc-mixed"]}


def ev(start, end, name, **stats):
    return (start * MS, (end - start) * MS, name, list(stats.items()) or None)


def engine_line(shift=0.0):
    """Two whole steps, and the phases of a step cut at either edge."""
    spans = [
        (-5, -1, "engine.emit"),                  # its step began untraced
        (0, 30, "engine.step"), (0, 2, "engine.plan"),
        (2, 6, "engine.dispatch"), (6, 25, "engine.read"),
        (25, 30, "engine.emit"),
        (32, 60, "engine.step"), (32, 33, "engine.plan"),
        (33, 36, "engine.dispatch"), (36, 55, "engine.read"),
        (55, 60, "engine.emit"),
        (62, 63, "engine.plan"), (63, 66, "engine.dispatch")]   # cut
    out = []
    for i, (a, b, name) in enumerate(spans):
        stats = dict(step=i, t_mono=100.0 + a / 1e3, t_wall=5e9) \
            if name == "engine.step" else {}
        out.append(ev(a + shift, b + shift, name, **stats))
    return ("/host:CPU", [("inference-engine", out),
                          ("other", [ev(0, 99, "sleep")])])


def device(name="/device:TPU:0", ops_b=(35, 54)):
    return (name, [
        ("XLA Modules", [ev(4, 24, "jit_decode(1)"),
                         ev(35, 54, "jit_decode(1)"),
                         ev(64, 70, "jit_prefill(2)")]),
        # a program with an inner gap [10, 12), an op nested in another
        ("XLA Ops", [ev(4, 10, "%while"), ev(5, 8, "%fusion.1"),
                     ev(12, 24, "%fusion.2"), ev(*ops_b, "%fusion.3"),
                     ev(64, 70, "%fusion.4")]),
        ("Steps", [ev(0, 70, "0")])])


def test_idle_is_split_by_phase_and_adds_up():
    r = hs.reduce_planes([engine_line(), device()])
    assert r["steps"] == 2 and r["devices"] == 1
    assert r["range_s"] == pytest.approx(0.060)
    assert r["step_ms_mean"] == pytest.approx(29.0)
    idle = r["idle_ms_per_step"]
    # [0, 4) is plan 2 + dispatch 2; [24, 35) read 1, emit 5, between the
    # steps 2, plan 1, dispatch 2; [54, 60) read 1, emit 5: a step, halves
    assert idle == pytest.approx({"plan": 1.5, "dispatch": 2.0, "read": 1.0,
                                  "emit": 5.0, "outside": 1.0,
                                  "in_program": 1.0})
    # the six add up to the range less the ops' union: 60 - (6 + 12 + 19)
    assert sum(idle.values()) * r["steps"] == pytest.approx(60 - 37)
    assert r["phase_cover_min"] == pytest.approx(1.0)
    # the steps' anchors: trace ns less monotonic ns, one number
    assert r["mono_to_trace_ns"] == pytest.approx(-100.0 * 1e9)
    assert r["mono_to_trace_spread_ns"] == pytest.approx(0.0, abs=1.0)


def test_a_stretch_is_split_over_the_phases_it_overlaps():
    r = hs.reduce_planes([engine_line(), device()])
    longest = r["longest_between_programs"][0]
    assert longest[0] == pytest.approx(0.011) and longest[1] == "emit"
    assert longest[2] == pytest.approx({
        "read": 0.001, "emit": 0.005, "outside": 0.002, "plan": 0.001,
        "dispatch": 0.002})
    assert [round(x[0], 3) for x in r["longest_between_programs"]] == [
        0.011, 0.006, 0.004]


def test_a_stretch_under_no_step_is_outside():
    by = hs._split((30 * MS, 32 * MS), [(25 * MS, 30 * MS, "emit"),
                                        (32 * MS, 33 * MS, "plan")])
    assert by["outside"] == 2 * MS and sum(by.values()) == 2 * MS


def test_a_step_cut_by_the_traces_edge_is_left_out():
    (line,) = hs._engine_lines([engine_line()])
    steps, phases = hs._whole_steps(line)
    assert [(a / MS, b / MS) for a, b, _ in steps] == [(0, 30), (32, 60)]
    assert len(phases) == 8                   # of 11: three have no step
    assert min(s for s, _, _ in phases) == 0
    assert max(e for _, e, _ in phases) == 60 * MS
    # and the program that ran past the last whole step is not counted
    r = hs.reduce_planes([engine_line(), device()])
    assert r["dispatch_to_program"]["programs"] == 2


def test_two_device_planes_are_averaged():
    r = hs.reduce_planes([engine_line(), device(),
                          device("/device:TPU:1", ops_b=(35, 50))])
    assert r["devices"] == 2
    idle = r["idle_ms_per_step"]
    assert idle["in_program"] == pytest.approx((2 + 6) / 2 / 2)
    assert idle["emit"] == pytest.approx(5.0)     # the same on both


def test_no_engine_step_reads_none_everywhere():
    host = ("/host:CPU", [("python", [ev(0, 99, "sleep")])])
    assert hs.reduce_planes([host, device()]) is None
    assert hs.reduce_planes([device()]) is None
    assert hs.reduce_planes([engine_line()]) is None      # no device plane
    assert hs.reduce_planes([]) is None
    for run in ({"kind": "none"}, {"traced": None, "cell": "x"},
                {"traced": (0.0, 1.0), "cell": "no-such-cell"}):
        assert hs.of_run(run) is None
        assert all(hs.idle_ms(run, k) is None for k in hs.KINDS)


def test_a_recorded_trace_without_the_spans_reads_none():
    """The trace recorded on a TPU v5e before the engine marked its steps
    (perfbench/data, a scratch program): JAX's own reader gives the
    device's lines, and there is no `engine.step` to read."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data",
                        "tiny_step.xplane.pb")
    planes = list(hs.read_xplane(path))
    device = dict(next(ls for name, ls in planes
                       if name.startswith("/device:")))
    assert len(device["XLA Modules"]) == 4 and device["XLA Ops"]
    assert hs.reduce_planes(planes) is None


def test_programs_are_laid_against_the_dispatch_that_issued_them():
    lag = hs.reduce_planes([engine_line(), device()])["dispatch_to_program"]
    assert lag == {"programs": 2, "unissued": 0, "unrun": 0, "late": 0,
                   "lag_ms_median": pytest.approx(2.0),
                   "lag_ms_max": pytest.approx(2.0)}
    # a host clock 3 ms ahead of the device's: the first program began
    # before any dispatch span did, the second pairs with the span of the
    # step before its own, a step late, and the last span ran nothing
    off = hs.reduce_planes([engine_line(shift=3.0),
                            device()])["dispatch_to_program"]
    assert off == {"programs": 1, "unissued": 1, "unrun": 1, "late": 1,
                   "lag_ms_median": pytest.approx(30.0),
                   "lag_ms_max": pytest.approx(30.0)}
    # one a millisecond behind: the pairs hold, the lag is a millisecond more
    off = hs.reduce_planes([engine_line(shift=-1.0),
                            device()])["dispatch_to_program"]
    assert (off["programs"], off["late"], off["lag_ms_median"]) == (
        2, 0, pytest.approx(3.0))


def test_a_device_clock_that_reads_early_is_laid_on_the_hosts():
    """Ten programs a 20 ms apart on a device whose clock reads 1.5 ms
    early; the runtime's host events say when each was enqueued (0.1-0.2 ms
    before it began) and when the host heard of its end (0.1-0.4 ms
    after): the shift is the middle of the two bounds."""
    real = [(20.0 * i + 3, 20.0 * i + 19) for i in range(10)]
    steps = []
    for i, (a, b) in enumerate(real):
        t = 20.0 * i
        steps += [ev(t, t + 20, "engine.step", step=i, t_mono=t / 1e3),
                  ev(t, t + 1, "engine.plan"),
                  ev(t + 1, t + 2.5, "engine.dispatch"),
                  ev(t + 2.5, t + 19.5, "engine.read"),
                  ev(t + 19.5, t + 20, "engine.emit")]
    runtime = [ev(a - (0.2 if i % 2 else 0.1), a - 0.05, hs.ENQUEUE)
               for i, (a, b) in enumerate(real)] \
        + [ev(b + (0.1 if i % 3 else 0.4), b + 0.5, hs.DONE)
           for i, (a, b) in enumerate(real)]
    host = ("/host:CPU", [("inference-engine", steps),
                          ("tfrt-non-blocking-queue", runtime)])
    early = ("/device:TPU:0", [
        ("XLA Modules", [ev(a - 1.5, b - 1.5, "jit_decode(1)")
                         for a, b in real]),
        ("XLA Ops", [ev(a - 1.5, b - 1.5, "%fusion") for a, b in real])])
    other = ("/device:CUSTOM:Megascale Trace", [])    # holds no program
    r = hs.reduce_planes([host, early, other])
    assert r["device_clock"] == pytest.approx({
        "shift_ns": 1.5 * MS, "at_least_ns": 1.4 * MS,
        "at_most_ns": 1.6 * MS, "programs": 10})
    # on the host's clock a program runs [t + 3, t + 19): idle 2 ms under
    # plan and dispatch, 1 under read (0.5 before, 0.5 after), 0.5 emit
    assert r["idle_ms_per_step"] == pytest.approx({
        "plan": 1.0, "dispatch": 1.5, "read": 1.0, "emit": 0.5,
        "outside": 0.0, "in_program": 0.0}, abs=1e-9)
    assert r["dispatch_to_program"]["late"] == 0
    assert r["dispatch_to_program"]["lag_ms_median"] == pytest.approx(2.0)
    # without the runtime's events nothing is shifted, and it shows: the
    # first program began before any dispatch span had
    bare = hs.reduce_planes([(host[0], host[1][:1]), early])
    assert bare["device_clock"] is None
    assert bare["idle_ms_per_step"]["read"] == pytest.approx(2.0)
    assert bare["dispatch_to_program"]["unissued"] == 0 \
        and bare["dispatch_to_program"]["lag_ms_median"] == pytest.approx(0.5)
    # two device planes with programs: whose the events are is not said
    assert hs.reduce_planes([host, early, ("/device:TPU:1", early[1])])[
        "device_clock"] is None


def test_the_slice_is_laid_on_the_trace_by_the_anchors():
    # t_mono 100.000 s is trace time 0: the harness's slice [100, 100.06)
    # is the whole steps' range, and the op after it is busy outside it
    r = hs.reduce_planes([engine_line(), device()], window=(100.0, 100.06))
    assert r["window"] == pytest.approx({"idle_s": 0.023,
                                         "busy_outside_s": 0.006})
    assert r["window"]["idle_s"] * 1e3 == pytest.approx(
        sum(r["idle_ms_per_step"].values()) * r["steps"])
    wide = hs.reduce_planes([engine_line(), device()],
                            window=(99.998, 100.072))
    assert wide["window"] == pytest.approx({"idle_s": 0.031,
                                            "busy_outside_s": 0.0})
    assert hs.reduce_planes([engine_line(), device()])["window"] is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_an_entry_and_a_file(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"] == NEW[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"][:24]}
    read = spec.load_reader(BENCH, name)
    assert read({"kind": "none"}) is None
    # a program without the counters (the parent's) is read as nothing
    old = {"counters": {"t0": {"steps": 1}, "t1": {"steps": 5}}}
    assert read(old) is None


def test_the_counters_readers_divide_what_they_say():
    t0 = dict(admitted=2, queue_wait_s=1.0, first_tokens=1,
              prefill_span_s=0.5, fused_steps=10, steps=100,
              dsa_rows_streamed=0, dsa_rows_live=0)
    t1 = dict(admitted=6, queue_wait_s=1.2, first_tokens=5,
              prefill_span_s=2.5, fused_steps=40, steps=200,
              dsa_rows_streamed=520, dsa_rows_live=500)
    run = {"counters": {"t0": t0, "t1": t1}}
    want = {"queue_wait_ms": 50.0, "prefill_span_ms": 500.0,
            "fused_step_share": 30.0, "dsa_streamed_per_live": 1.04}
    for name, value in want.items():
        assert spec.load_reader(BENCH, name)(run) == pytest.approx(value)
        assert spec.load_reader(BENCH, name)(
            {"counters": {"t0": t0, "t1": t0}}) is None    # nothing moved
