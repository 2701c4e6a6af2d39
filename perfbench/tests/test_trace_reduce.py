import os

import pytest

from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data")


def test_union_and_gaps():
    busy, gaps = tr._union([(0, 10), (5, 12), (20, 30), (22, 25)])
    assert busy == 22 and gaps == [(12, 20)]
    assert tr._union([]) == (0.0, [])


def test_self_time_takes_children_out():
    # a `while` of 100 holding two body ops of 30 and 40, then a lone op
    ev = [(0, 100, "while"), (10, 30, "fusion.1"), (50, 40, "fusion.2"),
          (200, 25, "fusion.1")]
    st = tr._self_times(ev)
    assert st["while"] == 30 and st["fusion.1"] == 55 and st["fusion.2"] == 40
    assert sum(st.values()) == 125               # the busy union


def test_reduce_synthetic_planes():
    ms = 1_000_000
    dev = ("/device:TPU:0", [
        ("XLA Modules", [(0, 10 * ms, "jit_decode(123)"),
                         (20 * ms, 12 * ms, "jit_decode(123)"),
                         (40 * ms, 5 * ms, "jit_prefill(9)")]),
        ("XLA Ops", [(0, 10 * ms, "while"), (1 * ms, 8 * ms, "fusion.7"),
                     (20 * ms, 12 * ms, "while"),
                     (21 * ms, 10 * ms, "fusion.7"),
                     (40 * ms, 5 * ms, "fusion.9")]),
        ("Steps", [(0, 50 * ms, "0")])])
    host = ("/host:CPU", [("python", [(0, 99 * ms, "sleep")])])
    r = tr.reduce_planes([dev, host])
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.027)
    assert r["first_to_last_op_s"] == pytest.approx(0.045)
    assert r["programs"]["jit_decode"]["count"] == 2
    assert r["programs"]["jit_decode"]["total_s"] == pytest.approx(0.022)
    assert r["programs"]["jit_prefill"]["durations_s"] == [0.005]
    assert r["op_self_s"]["fusion.7"] == pytest.approx(0.018)
    assert r["op_self_s"]["while"] == pytest.approx(0.004)
    assert r["idle_gaps_s"] == pytest.approx([0.010, 0.008])
    b, programs = tr.breakdown(r)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert b["device_ops"][0] == ["fusion.7", pytest.approx(0.018)]
    assert len(b["idle_gaps"]) == 2 and programs[0][0] == "jit_decode"
    assert tr.reduce_planes([host]) == {}


def test_op_name_keeps_name_and_shape():
    long = ("%fusion.180 = bf16[16,14336]{1,0:T(8,128)(2,1)S(1)} fusion("
            "bf16[20,4096,14336]{2,1,0} %get-tuple-element.540), kind=kOutput")
    assert tr.op_name(long) == "fusion.180 bf16[16,14336]"
    assert tr.op_name("%while = (s32[]{:T(128)}, bf16[2,2]) while(...)") \
        == "while s32[]"           # a tuple: its first element's shape
    assert tr.op_name("no equals sign") == "no equals sign"


def test_recorded_trace_from_the_chip():
    """A trace recorded on a TPU v5e (scratch program `tiny_step`: a scan
    of four matmuls, run four times) kept in perfbench/data."""
    path = os.path.join(DATA, "tiny_step.xplane.pb")
    r = tr.reduce_planes(tr.read_xplane(path))
    assert r["devices"] == 1
    prog = r["programs"]["jit_tiny_step"]
    assert prog["count"] == 4
    assert 0 < r["busy_s"] <= prog["total_s"] * 1.001
    assert r["busy_s"] < r["first_to_last_op_s"]
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"],
                                                         rel=1e-6)
    assert r["n_idle_gaps"] >= 3
