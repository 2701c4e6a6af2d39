"""`tile_kernel_share` as the harness meets it (PR 48): the entry names the
two serving cells whose tiles go through `_tile_attention` and the metric
they report, and the reader divides the engine's pair of counters and reads
nothing where a program has none (the parent's, a dense model's) or the
window held no prefill dispatch."""
import pytest

from perfbench import spec

BENCH = spec.load_benchmark()
NAME = "tile_kernel_share"
CELLS = {"trinity-large-preview.longdoc-report": "afmoe",
         "falcon-h1-34b.rag-answer": "falcon_h1"}


def test_the_metric_is_an_entry_and_a_file():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Kernels, serving",
        "moves": "out_tok_s", "workloads": list(CELLS)}
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == "out_tok_s"]
    for cell, family in CELLS.items():
        assert cell in moved["workloads"]
        (cfg,) = [w["config"] for w in BENCH["workloads"]
                  if w["name"] == cell]
        assert spec.load_config(BENCH, cfg)["family"] == family
    assert spec.load_reader(BENCH, NAME).__code__.co_filename.endswith(
        "metrics/tile_kernel_share.py")


@pytest.mark.parametrize("took,share", [(0, 0.0), (300, 100.0), (240, 80.0)])
def test_the_reader_divides_the_pair(took, share):
    read = spec.load_reader(BENCH, NAME)
    # 60 prefill dispatches of a five-layer stage between the two reads
    run = {"counters": {
        "t0": {"steps": 10, "tile_attn_layers": 50,
               "tile_kernel_layers": took // 6},
        "t1": {"steps": 200, "tile_attn_layers": 350,
               "tile_kernel_layers": took // 6 + took}}}
    assert read(run) == pytest.approx(share)


def test_the_reader_reads_nothing_without_the_pair():
    read = spec.load_reader(BENCH, NAME)
    assert read({"kind": "none"}) is None
    # no prefill dispatch in the window
    same = {"steps": 20, "tile_attn_layers": 350, "tile_kernel_layers": 350}
    assert read({"counters": {"t0": same, "t1": same}}) is None
    # a program without the counters (the parent's, the dense model's)
    assert read({"traced": (1.0, 5.0), "cell": "no-such-cell",
                 "config": spec.load_config(BENCH, "mistral-7b"),
                 "counters": {"t0": {"steps": 1, "kv_rows_live": 3},
                              "t1": {"steps": 2, "kv_rows_live": 9}},
                 "records": []}) is None
