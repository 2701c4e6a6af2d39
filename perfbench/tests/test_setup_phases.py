"""The set-up's phases (PR 56): `perfbench/setup_phases.py` and the seven
readers over it, from a hand-made `run`."""

import pytest

from perfbench import setup_phases, spec

BENCH = spec.load_benchmark()
TILING = ("setup_launch_s", "setup_weights_s", "setup_engine_s",
          "setup_rest_s")
SECOND_CUT = ("setup_trace_lower_s", "setup_compile_s",
              "compile_cache_hit_share")
SERVING = [w["name"] for w in BENCH["workloads"][:9]
           if w["name"] != "mistral-7b-train.pretrain-4k"]


def a_run(**t0):
    """The harness started at 1000.0 on the host's monotonic clock, the
    worker entered the replica's `__init__` at 1004.5 and the window
    opened at 1071.123456."""
    counters = dict(
        steps=7, startup_t_mono=1004.5, startup_init_s=41.25,
        startup_weights_s=19.375, startup_engine_s=18.0625,
        startup_pools_s=0.5, startup_programs_s=17.5, xla_compiles=200,
        xla_cache_hits=30, xla_cache_misses=0, xla_trace_s=9.5,
        xla_lower_s=4.25, xla_compile_s=12.75, xla_cache_load_s=6.0)
    counters.update(t0)
    return {"kind": "serve", "t_win0": 1071.123456, "setup_s": 71.123456,
            "mix": {"driver": "open", "ramp_s": 10.0},
            "counters": {"t0": counters, "t1": dict(counters, steps=90)}}


def read(name, run):
    return spec.load_reader(BENCH, name)(run)


def test_the_four_phases_and_the_ramp_tile_setup_s():
    run = a_run()
    parts = {name: read(name, run) for name in TILING}
    assert parts["setup_launch_s"] == pytest.approx(4.5, abs=1e-9)
    assert parts["setup_weights_s"] == 19.375
    assert parts["setup_engine_s"] == 18.0625
    assert setup_phases.ramp_s(run) == 10.25
    assert parts["setup_rest_s"] == pytest.approx(18.935956, abs=1e-6)
    assert sum(parts.values()) + setup_phases.ramp_s(run) == pytest.approx(
        run["setup_s"], abs=1e-6)
    # whatever the stamps, the sum is setup_s: a mix with no ramp, a late
    # worker
    run = a_run(startup_t_mono=1031.0625)
    run["mix"] = {"driver": "closed"}
    assert setup_phases.ramp_s(run) == 0.25
    assert sum(read(name, run) for name in TILING) + 0.25 == pytest.approx(
        run["setup_s"], abs=1e-6)


def test_the_second_cut_reads_the_totals_at_the_windows_first_instant():
    run = a_run()
    run["counters"]["t1"].update(xla_compiles=201, xla_compile_s=99.0)
    assert read("setup_trace_lower_s", run) == 13.75
    assert read("setup_compile_s", run) == 12.75
    assert read("compile_cache_hit_share", run) == 15.0
    assert read("compile_cache_hit_share", a_run(xla_compiles=0)) is None


@pytest.mark.parametrize("name", TILING + SECOND_CUT)
def test_a_reader_gives_none_without_the_counters(name):
    assert read(name, {"kind": "none"}) is None
    # the parent's program: counters, none of these
    old = a_run()
    for c in old["counters"].values():
        for key in [k for k in c if k.startswith(("startup_", "xla_"))]:
            del c[key]
    assert read(name, old) is None
    # the training cell's run carries no counter of the program
    assert read(name, {"kind": "train", "setup_s": 33.5}) is None


@pytest.mark.parametrize("name", TILING + SECOND_CUT)
def test_each_new_metric_is_an_entry_and_a_file(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["moves"] == "setup_s"
    assert entry["workloads"][:8] == SERVING
    assert "mistral-7b-train.pretrain-4k" not in entry["workloads"]
    stamps = name in ("setup_launch_s", "setup_rest_s")
    assert entry["source"] == ("host_clock" if stamps
                               else "program_counter")
    assert entry["unit"] == ("%" if name.endswith("share") else "s")
    assert callable(spec.load_reader(BENCH, name))
    for cell in SERVING:
        assert entry in spec.metrics_of(BENCH, cell, "per_layer")
