"""The benchmark's data: every file loads, every name resolves, and a
later PR adds a configuration, a mix and a metric with new files and new
entries only."""
import json
import os
import re
import shutil

import pytest

from perfbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_root")


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert 2 + 14 * 24 * 0 + (BENCH["run_seconds"] + 60) * (2 + 14 * 24) \
        + 24 * 180 + 1200 <= 43200
    assert cells <= 24
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for e in BENCH["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    assert any(e["name"] == "setup_s" and "workloads" not in e
               for e in BENCH["end_to_end"])


def test_every_config_and_mix_loads_and_maps_to_the_program():
    for w in BENCH["workloads"]:
        cfg = spec.load_config(BENCH, w["config"])
        mix = spec.load_traffic(BENCH, w["traffic"])
        kw = spec.transformer_kwargs(cfg)
        assert kw["d_model"] == 4096 and kw["d_ff"] == 14336   # no width cut
        assert kw["n_heads"] == 32 and kw["n_kv_heads"] == 8
        assert mix["driver"] in ("open", "closed", "train")
        for key in ("source", "stands_for", "reduced", "assumed",
                    "reference_tolerance"):
            assert key in cfg, (w["config"], key)
        assert sorted(cfg["reduced"]) == sorted(cfg["_entry"]["reduced"])
        assert cfg["source"] == cfg["_entry"]["source"]


def test_a_passed_sliding_window_is_refused():
    cfg = spec.load_config(BENCH, "mistral-7b")
    cfg["engine"] = dict(cfg["engine"], max_len=8192)
    with pytest.raises(spec.SpecError):
        spec.transformer_kwargs(cfg)


def test_every_metric_has_a_reader_and_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = w["name"]
        reported = {m["name"] for m in spec.metrics_of(BENCH, cell,
                                                       "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = spec.metrics_of(BENCH, cell, "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in reported, (cell, m["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(spec.load_reader(BENCH, m["name"]))
        assert spec.load_reader(BENCH, m["name"])({"kind": "none"}) is None \
            or m["name"] == "setup_s"
        for cell in m.get("workloads", []):
            spec.workload(BENCH, cell)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    root = str(tmp_path / "root")
    shutil.copytree(FIXTURE, root)
    before = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    # new files: a second directory of the later PR's own
    new = os.path.join(root, "bench2")
    for sub in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(new, sub))
    cfg = json.load(open(os.path.join(
        root, "bench", "configs", "tiny-mistral.json")))
    cfg.update(name="tiny-wide", intermediate_size=256)
    json.dump(cfg, open(os.path.join(new, "configs", "tiny-wide.json"), "w"))
    mix = json.load(open(os.path.join(root, "bench", "traffic", "open.json")))
    mix["arrival"] = {"process": "gamma", "cv": 2.5, "rate_per_s": 6.0}
    json.dump(mix, open(os.path.join(new, "traffic", "burst.json"), "w"))
    with open(os.path.join(new, "metrics", "queue_depth_end.py"), "w") as f:
        f.write("def read(run):\n"
                "    c = run.get('counters') or {}\n"
                "    return c['t1']['queue_depth'] if 't1' in c else None\n")
    # new entries
    bench["paths"].append("bench2")
    bench["configs"].append({
        "name": "tiny-wide", "source": "test", "reduced": [], "why": "t",
        "file": "bench2/configs/tiny-wide.json"})
    bench["workloads"].append({
        "name": "tiny-wide.burst", "config": "tiny-wide",
        "traffic": "burst", "chips": 1, "why": "t"})
    bench["per_layer"].append({
        "name": "queue_depth_end", "unit": "requests", "better": "lower",
        "source": "program_counter", "layer": "Engine", "moves": "setup_s",
        "workloads": ["tiny-wide.burst"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    # the harness finds all three by name
    b = spec.load_benchmark(root)
    cell = spec.workload(b, "tiny-wide.burst")
    assert spec.load_config(b, cell["config"], root)["intermediate_size"] \
        == 256
    assert spec.load_traffic(b, cell["traffic"], root)["arrival"]["cv"] \
        == 2.5
    (m,) = spec.metrics_of(b, "tiny-wide.burst", "per_layer")
    read = spec.load_reader(b, m["name"], root)
    assert read({"counters": {"t1": {"queue_depth": 3}}}) == 3
    assert read({}) is None
    assert spec.metrics_of(b, "tiny-mistral.open", "per_layer") == []
    # and no file that was there changed, but BENCHMARK.json
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == data
