"""`tile_head_time_share` as the harness meets it (PR 45: one new entry and
one new file, held as tests/test_falcon_h1_family.py holds PR 44's four
readers): its cells are serving cells that report the metric it moves, and
the reader reads the tile program's `lm_head` scope in a traced run and
nothing where there is no trace or no such scope (the parent's program)."""
import pytest

from perfbench import scope_times, spec

BENCH = spec.load_benchmark()
NAME = "tile_head_time_share"


def test_the_metric_is_an_entry_and_a_file():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert BENCH["per_layer"][-1] is entry
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        "%", "lower", "device_trace", "Model step", "out_tok_s")
    (moved,) = [m for m in BENCH["end_to_end"]
                if m["name"] == entry["moves"]]
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert len(entry["workloads"]) == 3
    for name in entry["workloads"]:
        assert name in moved["workloads"]
        cfg = spec.load_config(BENCH, cells[name]["config"])
        # the cells whose tile is 1,024 rows: there the head over every
        # row was a share of the program worth a metric
        assert cfg["engine"]["prefill_budget"] == 1024
    read = spec.load_reader(BENCH, NAME)
    assert read({"kind": "none"}) is None
    assert read({"traced": (1.0, 5.0), "cell": "no-such-cell",
                 "config": spec.load_config(BENCH, "mistral-7b"),
                 "records": []}) is None


def test_the_reader_divides_what_it_says(monkeypatch):
    """A made-up traced slice: 5 tile steps of 40 ms, 3.5 ms of each inside
    `lm_head`; the decode program's executions do not enter."""
    asked = []

    def scope_seconds(run, scope, program):
        asked.append((scope, program))
        return {("lm_head", "jit_prefill"): (0.0175, 5)}.get(
            (scope, program))
    monkeypatch.setattr(scope_times, "scope_seconds", scope_seconds)
    run = {"traced": (100.0, 104.0), "cell": "falcon-h1-34b.rag-answer",
           "trace": {"programs": {
               "jit_prefill": {"durations_s": [0.040] * 5},
               "jit_decode": {"durations_s": [0.018] * 20}}}}
    read = spec.load_reader(BENCH, NAME)
    assert read(run) == pytest.approx(3.5 / 40 * 100)
    assert asked == [("lm_head", "jit_prefill")]
    assert read(dict(run, traced=None)) is None
    # a program compiled before the scope had a name: nothing, no error
    monkeypatch.setattr(scope_times, "scope_seconds", lambda *a: None)
    assert read(run) is None
