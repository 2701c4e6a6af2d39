"""The benchmark's data: every file loads, every name resolves, and a
later PR adds a configuration, a mix and a metric with new files and new
entries only."""
import json
import os
import re

import pytest
from later_pr import add_later_pr

from perfbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


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
        kw = spec.family_of(cfg).model_kwargs(cfg)
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
        spec.family_of(cfg).model_kwargs(cfg)


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
    before = add_later_pr(root)
    # the harness finds a configuration, a mix and a reader by name
    b = spec.load_benchmark(root)
    cell = spec.workload(b, "tiny-wide.burst")
    wide = spec.load_config(b, cell["config"], root)
    assert wide["intermediate_size"] == 256
    assert spec.load_traffic(b, cell["traffic"], root)["arrival"]["cv"] \
        == 2.5
    (m,) = spec.metrics_of(b, "tiny-wide.burst", "per_layer")
    read = spec.load_reader(b, m["name"], root)
    assert read({"counters": {"t1": {"queue_depth": 3}}}) == 3
    assert read({}) is None
    assert spec.metrics_of(b, "tiny-mistral.open", "per_layer") == []
    # and a family: the new one under the later PR's directory, the old
    # one where it was, each with its own mapping and its own counts
    other = spec.load_config(b, "tiny-other", root)
    fam = spec.family_of(other)
    assert fam.__file__ == os.path.join(root, "bench2", "families",
                                        "other.py")
    assert fam.model_kwargs(other)["d_model"] == 64
    assert spec.family_of(wide).__file__ == os.path.join(
        root, "bench", "families", "mistral.py")
    with pytest.raises(KeyError):            # other key names
        spec.family_of(wide).model_kwargs(other)
    with pytest.raises(KeyError):            # and a leaf it refuses
        spec.family_of(wide).weight_rule(["logit_bias"], (256,))
    assert fam.weight_rule(["logit_bias"], (256,)) == (1.0, False)
    assert fam.stored_param_bytes(other, 2.0) == 2.0 * (
        2 * (2 * 64 * 6 * 16 + 3 * 64 * 128) + 2 * 256 * 64)
    # what travels to a worker is enough to find the same file there
    travels = json.loads(json.dumps(
        {k: v for k, v in other.items() if k != "_entry"}))
    assert spec.family_of(travels) is fam
    # and no file that was there changed, but BENCHMARK.json
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == data


def test_a_configuration_without_a_family_names_its_file(tmp_path):
    root = str(tmp_path / "root")
    add_later_pr(root)
    path = os.path.join(root, "bench2", "configs", "tiny-wide.json")
    cfg = json.load(open(path))
    del cfg["family"]
    json.dump(cfg, open(path, "w"))
    with pytest.raises(spec.SpecError, match="tiny-wide.json"):
        spec.load_config(spec.load_benchmark(root), "tiny-wide", root)
    cfg["family"] = "nobodys"
    json.dump(cfg, open(path, "w"))
    with pytest.raises(spec.SpecError, match="families/nobodys.py"):
        spec.load_config(spec.load_benchmark(root), "tiny-wide", root)


@pytest.mark.parametrize("missing", spec.FAMILY_STATES)
def test_a_family_that_leaves_a_statement_out_names_its_file(tmp_path,
                                                              missing):
    root = str(tmp_path / "root")
    add_later_pr(root)
    path = os.path.join(root, "bench2", "families", "other.py")
    text = open(path).read()
    assert f"def {missing}(" in text
    open(path, "w").write(text.replace(f"def {missing}(",
                                       f"def _{missing}("))
    with pytest.raises(spec.SpecError, match=f"other.py.*{missing}"):
        spec.load_config(spec.load_benchmark(root), "tiny-other", root)
