"""`kv_streamed_per_live` as the harness meets it (PR 47: one reader, the
twin of `win_streamed_per_live.py`, under two names because its two cells
report different end-to-end metrics): the entries name serving cells of the
dense model that report the metric they move, and the reader divides the
engine's pair of counters and reads nothing where a program has none (the
parent's, another family's)."""
import pytest

from perfbench import spec

BENCH = spec.load_benchmark()
ENTRIES = {"kv_streamed_per_live": ("mistral-7b.chat-steady", "tpot_p95_ms"),
           "kv_streamed_per_live.tok": ("mixtral-8x7b.batch-longprompt",
                                        "out_tok_s")}


@pytest.mark.parametrize("name", list(ENTRIES))
def test_the_metric_is_an_entry_and_one_file(name):
    cell, moves = ENTRIES[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"], entry["workloads"]) == (
        "rows/row", "lower", "program_counter", "Kernels, serving", moves,
        [cell])
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == moves]
    assert cell in moved["workloads"]
    (cfg,) = [w["config"] for w in BENCH["workloads"] if w["name"] == cell]
    assert spec.load_config(BENCH, cfg)["family"] == "mistral"
    # both names read through the one file
    assert spec.load_reader(BENCH, name).__code__.co_filename.endswith(
        "metrics/kv_streamed_per_live.py")


@pytest.mark.parametrize("name", list(ENTRIES))
def test_the_reader_divides_the_pair_and_reads_nothing_without_it(name):
    read = spec.load_reader(BENCH, name)
    # 40 decode rows at lengths near 400: a block of 512 and the row's own
    run = {"counters": {"t0": {"steps": 10, "kv_rows_streamed": 5130,
                               "kv_rows_live": 4000},
                        "t1": {"steps": 20, "kv_rows_streamed": 25650,
                               "kv_rows_live": 20000}}}
    assert read(run) == pytest.approx(20520 / 16000)
    assert read({"kind": "none"}) is None
    # no decode row in the window
    assert read({"counters": {"t0": run["counters"]["t1"],
                              "t1": run["counters"]["t1"]}}) is None
    # a program without the counters (the parent's, an indexer's)
    assert read({"traced": (1.0, 5.0), "cell": "no-such-cell",
                 "config": spec.load_config(BENCH, "mistral-7b"),
                 "counters": {"t0": {"steps": 1, "dsa_rows_live": 3},
                              "t1": {"steps": 2, "dsa_rows_live": 9}},
                 "records": []}) is None
