"""The `sarvam_mla` family as the harness meets it (PR 50: new files and new
entries only): its configuration and mix load and map to the program, the
family's byte and FLOP counts are ISSUE 50's parameter arithmetic, and the
five readers read a traced run's scopes and counters and nothing where
there are none (the parent's program, an untraced run)."""
import json

import pytest

from perfbench import metrics_lib as ml, scope_times, spec

BENCH = spec.load_benchmark()
CELL = "sarvam-105b.longdoc-answer"
TRACED = ("mla_time_share", "mla_attend_roofline_share",
          "mla_row_roofline_share")
COUNTED = ("mla_pool_gb", "mla_streamed_per_live")
ROW = 576 * 2                         # a position's latent and rope key


@pytest.fixture(scope="module")
def cfg():
    return spec.load_config(BENCH, "sarvam-105b")


def test_configuration_and_mix_load_and_map_to_the_program(cfg):
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sarvam-105b", "longdoc-answer", 1)
    family = spec.family_of(cfg)
    kw = family.model_kwargs(cfg)
    assert (kw["d_model"], kw["d_ff"], kw["expert_d_ff"], kw["n_heads"],
            kw["head_dim"], kw["latent_dim"], kw["rope_dim"],
            kw["v_head_dim"], kw["vocab_size"], kw["n_layers"]) == (
        4096, 16384, 2048, 64, 192, 512, 64, 128, 32768, 8)
    assert kw["mixer_kinds"] == ["mla"] * 8 and kw["qk_norm"] is True
    assert kw["rope_yarn"] == [40, 4096, 32, 1, 1]
    assert (kw["n_experts"], kw["expert_top_k"], kw["experts_held"],
            kw["n_shared_experts"], kw["n_dense_layers"], kw["router"],
            kw["route_scale"], kw["capacity_factor"], kw["norm_eps"]) == (
        128, 8, [0, 16], 1, 1, "sigmoid", 2.5, 2.0, 1e-6)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "sarvam-105b"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 32
    for key in ("stands_for", "assumed", "bytes", "deployment",
                "published", "reference_tolerance"):
        assert cfg[key], key
    mix = spec.load_traffic(BENCH, cell["traffic"])
    assert (mix["driver"], mix["clients"]) in (("closed", 16), ("closed", 8))
    assert mix["prompt_len"] == {"dist": "uniform", "min": 6144, "max": 9216}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 512}
    assert (mix["population"], mix["ramp_s"], mix["trace_s"],
            mix["population_seed"]) == (512, 30.0, 4.0, 50)
    assert mix["reference_cases"] == [[1024, 256], [4096, 256], [9216, 256]]
    engine = cfg["engine"]
    # serve_cell cuts a reference case's prompt to max_len // 2
    assert max(p for p, _ in mix["reference_cases"]) <= engine["max_len"] // 2
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= engine["max_len"]
    # a prefix cache is refused by the family as by the engine; so is a
    # program that states no latent attention told apart from this one
    with pytest.raises(spec.SpecError):
        family.model_kwargs(dict(cfg, engine=dict(engine,
                                                  prefix_cache_slots=2)))
    with pytest.raises(spec.SpecError):
        family.model_kwargs(dict(cfg, q_lora_rank=1536))
    with pytest.raises(spec.SpecError):
        family.model_kwargs(dict(cfg, rope_scaling=dict(
            cfg["rope_scaling"], mscale=0.707)))


def test_every_number_of_the_catalogs_config_is_in_the_file(cfg):
    """The catalog's row, where this checkout can see it: every key of its
    `config` is in the file under the same key, and equal but for the
    three the entry lists as reduced."""
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") \
                as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog here")
    (row,) = [r for r in rows if r["name"] == "sarvam-105b"]
    assert row["source_url"] == cfg["source"]
    differ = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differ == {"num_hidden_layers", "vocab_size"}


def test_the_counts_are_the_parameter_arithmetic(cfg):
    family = spec.family_of(cfg)
    near = lambda x: pytest.approx(x, rel=1e-3)               # noqa: E731
    # ISSUE 50's reckoning: attention 94.6 M (q 50.33, kv_down 2.36, kv_up
    # 8.39, o 33.55), the dense layer 296.0 M, an expert layer on one of
    # eight chips 523.0 M, both tables' eighth 268.4 M: 4.225 G, 8.45 GB
    assert family._attn_params(cfg) == near(94.63e6) \
        == 4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256 + 64 * 128 * 4096
    assert family.layer_params(cfg, 0, 16) == near(296.0e6)
    assert family.layer_params(cfg, 1, 16) == near(523.0e6)
    assert family.layer_params(cfg, 1, 1) - family.layer_params(cfg, 1, 0) \
        == near(25.17e6)
    assert family.stored_param_bytes(cfg, 2.0) == near(8.45e9) \
        == near(2 * (296.0e6 + 7 * 523.0e6 + 268.4e6))
    assert family.latent_row_values(cfg) * 2 == ROW
    # a decode step: the weights but the embedding, and 1,152 B a position
    # a layer of a live slot, whatever the 64 heads
    idle = family.decode_step_bytes(cfg, [], 2.0, 2.0)
    assert idle == family.stored_param_bytes(cfg, 2.0) - 32768 * 4096 * 2
    assert family.decode_step_bytes(cfg, [9000.0, 7000.0], 2.0, 2.0) - idle \
        == family.mla_row_bytes(cfg, 16000, 2.0) == 8 * 16000 * ROW
    assert family.mla_row_flops(cfg, 16000) == 8 * 16000 * 64 * 2 * 1088
    # the row's bytes take twice its FLOPs' time on a v5e
    assert (ROW / 819e9) / (64 * 2 * 1088 / 197e12) == pytest.approx(
        1.99, abs=0.01)
    # a tile of 1,024 rows at position 4,096: causal pairs, q.k at 192 and
    # p.v at 128 a head, and its own rows' up-projection (512 x 16,384)
    pairs = family.causal_pairs(4096, 1024)
    assert pairs == sum(t + 1 for t in range(4096, 5120))
    assert family.mla_attend_flops(cfg, pairs, 1024) == 8 * (
        pairs * 64 * 2 * 320 + 1024 * 2 * 512 * 16384)
    assert family.mla_attend_bytes(cfg, 5120, 2.0) == 8 * 5120 * ROW
    assert family.causal_attention_flops(cfg, 2, 4096, False) \
        == 2 * family.mla_attend_flops(cfg, 4096 * 4097 / 2, 4096)
    assert family.train_step_flops(cfg, 1, 4096) > 0


@pytest.mark.parametrize("name", TRACED + COUNTED)
def test_a_new_metric_is_an_entry_and_a_file(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "out_tok_s"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"][:24]}
    read = spec.load_reader(BENCH, name)
    assert read({"kind": "none"}) is None
    # a run of a program without the scopes or counters (the parent's,
    # another family's): nothing to read, and no error
    assert read({"traced": (1.0, 5.0), "cell": "no-such-cell",
                 "config": spec.load_config(BENCH, "mistral-7b"),
                 "counters": {"t0": {"steps": 1}, "t1": {"steps": 2}},
                 "records": []}) is None


def test_the_cell_is_listed_where_its_line_has_a_number():
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", ())}
    assert {"out_tok_s", "ttft_mean_ms", "kv_pool_gb", "moe_time_share",
            "moe_rows_per_pick", "decode_roofline_share.tok",
            "tile_head_time_share", "tile_kernel_share"} <= listed
    assert not {n for n in listed if n.startswith(("win_", "att_", "dsa_",
                                                   "blk_", "ssd_", "ssm_"))}


def test_the_readers_divide_what_they_say(cfg, monkeypatch):
    """A made-up traced slice: 40 tile steps of 70 ms and 60 decode steps
    of 16 ms; the scopes' device times as `scope_times` would give them."""
    inside = {("mla_attend", "jit_prefill"): (1.600, 40),
              ("mla_row", "jit_prefill"): (0.120, 40),
              ("mla_row", "jit_decode"): (0.180, 60)}
    monkeypatch.setattr(scope_times, "scope_seconds",
                        lambda run, scope, program: inside.get(
                            (scope, program)))
    family = spec.family_of(cfg)
    run = {"traced": (100.0, 104.0), "cell": CELL, "config": cfg,
           "mix": {"driver": "closed"}, "t_win0": 60.0, "t_win1": 105.0,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {
               "jit_prefill": {"durations_s": [0.070] * 40},
               "jit_decode": {"durations_s": [0.016] * 60}}},
           "counters": {"t0": {"prefill_dispatches": 10,
                               "prefill_tokens": 10000,
                               "mla_rows_streamed": 0, "mla_rows_live": 0},
                        "t1": {"prefill_dispatches": 110,
                               "prefill_tokens": 110000,
                               "mla_rows_streamed": 51200,
                               "mla_rows_live": 40960,
                               "latent_pool_bytes": 2717908992}},
           # twelve requests decoding through the whole slice, one through
           # its first half, one that has only its first token
           "records": [{"arrivals": [90.0, 110.0], "prompt_len": 8192,
                        "sent": 70.0}] * 12
           + [{"arrivals": [98.0, 102.0], "prompt_len": 9000, "sent": 80.0},
              {"arrivals": [101.0], "prompt_len": 6144, "sent": 90.0}]}
    whole = 40 * 0.070 + 60 * 0.016
    read = {name: spec.load_reader(BENCH, name) for name in TRACED + COUNTED}
    assert read["mla_time_share"](run) == pytest.approx(1.900 / whole * 100)
    assert read["mla_pool_gb"](run) == pytest.approx(2.717908992)
    assert read["mla_streamed_per_live"](run) == 1.25
    # 1000 real rows a tile; a prompt's rows attend (p + 1) / 2 keys on
    # average, and the FLOPs bind
    prompts = [8192] * 12 + [9000, 6144]
    pairs = 1000.0 * sum(p * (p + 1) / 2 for p in prompts) / sum(prompts)
    floor = family.mla_attend_flops(cfg, pairs, 1000.0) / 197e12
    assert floor > family.mla_attend_bytes(cfg, 9216, 2.0) / 819e9
    assert read["mla_attend_roofline_share"](run) == pytest.approx(
        40 * floor / 1.600 * 100)
    # 12.5 slots live on average over the slice: the bytes bind
    live = ml.mean_live_tokens(run, 100.0, 104.0)
    assert 12.5 * 8192 < live < 12.5 * 9100
    floor = family.mla_row_bytes(cfg, live, 2.0) / 819e9
    assert floor > family.mla_row_flops(cfg, live) / 197e12
    assert read["mla_row_roofline_share"](run) == pytest.approx(
        60 * floor / 0.180 * 100)
    for name in TRACED:
        assert 0 < read[name](run) < 100
        assert read[name](dict(run, traced=None)) is None
