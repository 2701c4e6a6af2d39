"""The `ling3` family as the harness meets it (PR 58: new files and new
entries only): its configuration and mix load and map to the program, the
family's byte and FLOP counts are ISSUE 58's parameter arithmetic, and the
four readers read a traced run's scopes and counters and nothing where
there are none (the parent's program, an untraced run)."""
import json

import pytest

from perfbench import scope_times, spec
from perfbench.metrics.ssd_step_roofline_share import live_rows

BENCH = spec.load_benchmark()
CELL = "ling-3.0-flash-vl.longctx-wide"
TRACED = ("kda_time_share", "kda_scan_roofline_share",
          "kda_step_roofline_share")
COUNTED = ("moe_row_hit_share",)
NEW = tuple(n for n in TRACED + COUNTED
            if any(m["name"] == n for m in BENCH["per_layer"]))
STATE = 32 * 128 * 128 * 4            # one layer's float32 state of a slot


@pytest.fixture(scope="module")
def cfg():
    return spec.load_config(BENCH, "ling-3.0-flash-vl")


def test_configuration_and_mix_load_and_map_to_the_program(cfg):
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-3.0-flash-vl", "longctx-wide", 1)
    family = spec.family_of(cfg)
    kw = family.model_kwargs(cfg)
    assert (kw["d_model"], kw["d_ff"], kw["expert_d_ff"], kw["n_heads"],
            kw["head_dim"], kw["kda_head_dim"], kw["latent_dim"],
            kw["rope_dim"], kw["v_head_dim"], kw["vocab_size"],
            kw["n_layers"]) == (
        2560, 6144, 768, 32, 192, 128, 512, 64, 128, 19648, 13)
    assert kw["mixer_kinds"] == ["kda"] * 4 + ["mla"] + ["kda"] * 5 \
        + ["mla"] + ["kda"] * 2
    assert (kw["kda_conv"], kw["kda_gate_floor"], kw["rope_theta"],
            kw["qk_norm"], kw["tie_embeddings"]) == (4, -5.0, 6e6, True,
                                                     False)
    assert "rope_yarn" not in kw
    assert (kw["n_experts"], kw["expert_top_k"], kw["n_group"],
            kw["topk_group"], kw["experts_held"], kw["n_shared_experts"],
            kw["n_dense_layers"], kw["router"], kw["route_norm"],
            kw["route_scale"], kw["capacity_factor"], kw["norm_eps"]) == (
        512, 8, 8, 4, [128, 64], 1, 1, "sigmoid", True, 2.5, 6.0, 1e-6)
    # the rank holds exactly one routing group
    assert kw["experts_held"][1] == kw["n_experts"] // kw["n_group"] \
        and kw["experts_held"][0] % kw["experts_held"][1] == 0
    (entry,) = [c for c in BENCH["configs"]
                if c["name"] == "ling-3.0-flash-vl"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 42
    assert cfg["deployment"]["chips"] == 24 == cfg["deployment"][
        "chips_per_layer"] * cfg["deployment"]["pipeline_stages"]
    assert cfg["deployment"]["stage_layers"] == list(range(1, 14))
    for key in ("stands_for", "assumed", "bytes", "deployment",
                "published", "reference_tolerance"):
        assert cfg[key], key
    for left_out in ("vision tower", "multi-token prediction", "clamped"):
        assert left_out in cfg["stands_for"]
    mix = spec.load_traffic(BENCH, cell["traffic"])
    assert (mix["driver"], mix["clients"]) == ("closed", 32)
    assert mix["prompt_len"] in (
        {"dist": "uniform", "min": 3072, "max": 4608},
        {"dist": "uniform", "min": 2048, "max": 3072})      # the retreat
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 512}
    assert (mix["population"], mix["ramp_s"], mix["trace_s"],
            mix["population_seed"]) == (512, 30.0, 4.0, 58)
    assert [g for _, g in mix["reference_cases"]] == [256] * 3
    engine = cfg["engine"]
    assert (engine["n_slots"], engine["prefill_budget"]) == (32, 1024)
    # serve_cell cuts a reference case's prompt to max_len // 2
    assert max(p for p, _ in mix["reference_cases"]) \
        == mix["prompt_len"]["max"] <= engine["max_len"] // 2
    # what the family refuses: a prefix cache, as the engine does; a
    # clamped SwiGLU among the layers held; a low-rank gate; a router
    # without a selection bias
    for over in (dict(engine=dict(engine, prefix_cache_slots=2)),
                 dict(expert_swiglu_limit_list=[0] * 12 + [4]),
                 dict(no_kda_lora=False), dict(q_lora_rank=1536),
                 dict(moe_router_enable_expert_bias=False)):
        with pytest.raises(spec.SpecError):
            family.model_kwargs(dict(cfg, **over))


def test_every_number_of_the_catalogs_config_is_in_the_file(cfg):
    """The catalog's row, where this checkout can see it: every key of its
    `config` is in the file under the same key, and equal but for those
    the entry lists as reduced."""
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") \
                as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog here")
    (row,) = [r for r in rows if r["name"] == "Ling-3.0-flash-VL"]
    assert row["source_url"] == cfg["source"]
    differ = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differ == {"num_hidden_layers", "first_k_dense_replace",
                      "vocab_size", "expert_swiglu_limit_list",
                      "share_expert_swiglu_limit_list"}
    (entry,) = [c for c in BENCH["configs"]
                if c["name"] == "ling-3.0-flash-vl"]
    assert differ | {"num_local_experts"} == set(entry["reduced"])


def test_the_counts_are_the_parameter_arithmetic(cfg):
    family = spec.family_of(cfg)
    near = lambda x: pytest.approx(x, rel=1e-3)               # noqa: E731
    # ISSUE 58's reckoning: a KDA mixer 52.65 M, a latent mixer 31.88 M;
    # layer 1 99.84 M; a KDA expert layer on one of eight chips 437.35 M, a
    # latent one 416.58 M; both tables' eighth 100.6 M: 5,407 M, 10.81 GB
    assert family._kda_params(cfg) == near(52.65e6)
    assert family._latent_params(cfg) == near(31.88e6) \
        == 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 32 * 128 * 2560
    assert family.layer_params(cfg, 0, 64) == near(99.84e6)
    assert family.layer_params(cfg, 1, 64) == near(437.35e6)
    assert family.layer_params(cfg, 4, 64) == near(416.58e6)
    assert family.layer_params(cfg, 1, 1) - family.layer_params(cfg, 1, 0) \
        == near(5.898e6)
    assert family.param_count(cfg) == near(5407e6)
    assert family.stored_param_bytes(cfg, 2.0) == near(10.81e9)
    # what a slot keeps: 2.10 MB of state and 3 x 12,288 float32 of tails a
    # KDA layer, 1,152 B a position a latent layer
    assert family.state_bytes(cfg) == STATE == pytest.approx(2.10e6, rel=2e-3)
    assert family.tail_bytes(cfg) == 3 * 12288 * 4
    assert family.latent_row_values(cfg) * 2 == 1152
    # the recurrence: 4.7 MFLOP a row a layer, eleven layers
    assert family.kda_scan_flops(cfg, 1.0) == 11 * 32 * (
        6 * 128 ** 2 + 6 * 64 * 128) == near(11 * 4.72e6)
    assert family.kda_scan_bytes(cfg, 1000.0, 2.0) == 11 * (
        1000 * (32 * 128 * (4 * 2 + 4) + 4 * 32) + 2 * STATE)
    assert family.kda_step_bytes(cfg, 32.0) == 11 * 32 * 2 * STATE
    # a decode step: the weights but the embedding, a live slot's eleven
    # states in and out and its positions of two layers of latents
    idle = family.decode_step_bytes(cfg, [], 2.0, 2.0)
    assert idle == family.stored_param_bytes(cfg, 2.0) - 19648 * 2560 * 2
    assert family.decode_step_bytes(cfg, [4000.0, 3000.0], 2.0, 2.0) - idle \
        == 2 * 7000 * 1152 + 2 * 11 * 2 * STATE
    assert family.mla_row_bytes(cfg, 7000, 2.0) == 2 * 7000 * 1152
    assert family.mla_row_flops(cfg, 7000) == 2 * 7000 * 32 * 2 * 1088
    pairs = family.causal_pairs(2048, 1024)
    assert pairs == sum(t + 1 for t in range(2048, 3072))
    assert family.mla_attend_flops(cfg, pairs, 1024) == 2 * (
        pairs * 32 * 2 * 320 + 1024 * 2 * 512 * 8192)
    assert family.mla_attend_bytes(cfg, 3072, 2.0) == 2 * 3072 * 1152
    assert family.causal_attention_flops(cfg, 2, 4096, False) == 2 * (
        family.mla_attend_flops(cfg, 4096 * 4097 / 2, 4096)
        + family.kda_scan_flops(cfg, 4096))
    assert family.train_step_flops(cfg, 1, 4096) > 0


@pytest.mark.parametrize("name", NEW)
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
    # (not on `ttft_mean_ms` nor on the five lists that move it: its mean
    # TTFT spread by more than half that metric's bound, PERF.md section 6)
    assert "ttft_mean_ms" not in listed and "prefill_prog_ms" not in listed
    assert {"out_tok_s", "kv_pool_gb", "state_pool_gb",
            "mla_pool_gb", "moe_rows_per_pick", "tile_kernel_share",
            "kda_time_share", "moe_row_hit_share"} <= listed
    assert not {n for n in listed if n.startswith((
        "win_", "att_", "dsa_", "blk_", "ssd_", "ssm_", "s6_", "diff_",
        "xkv_", "lightning_"))}
    assert len(NEW) in (3, 4)


def test_the_readers_divide_what_they_say(cfg, monkeypatch):
    """A made-up traced slice: 40 tile steps of 60 ms and 80 decode steps
    of 20 ms; the scopes' device times as `scope_times` would give them."""
    inside = {("kda_scan", "jit_prefill"): (0.600, 40),
              ("kda_step", "jit_prefill"): (0.100, 40),
              ("kda_step", "jit_decode"): (0.240, 80),
              ("kda_conv", "jit_prefill"): (0.080, 40),
              ("kda_conv", "jit_decode"): (0.016, 80)}
    monkeypatch.setattr(scope_times, "scope_seconds",
                        lambda run, scope, program: inside.get(
                            (scope, program)))
    family = spec.family_of(cfg)
    run = {"traced": (100.0, 104.0), "cell": CELL, "config": cfg,
           "mix": {"driver": "closed"}, "t_win0": 60.0, "t_win1": 105.0,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {
               "jit_prefill": {"durations_s": [0.060] * 40},
               "jit_decode": {"durations_s": [0.020] * 80}}},
           "counters": {"t0": {"prefill_dispatches": 10,
                               "prefill_tokens": 10000,
                               "moe_rows_hit": 1000, "moe_rows_real": 2000},
                        "t1": {"prefill_dispatches": 110,
                               "prefill_tokens": 110000,
                               "moe_rows_hit": 451000,
                               "moe_rows_real": 1002000}},
           # 28 requests decoding through the whole slice, one through its
           # first half, one that has only its first token
           "records": [{"arrivals": [90.0, 110.0], "prompt_len": 4000,
                        "sent": 70.0}] * 28
           + [{"arrivals": [98.0, 102.0], "prompt_len": 3500, "sent": 80.0},
              {"arrivals": [101.0], "prompt_len": 3072, "sent": 90.0}]}
    whole = 40 * 0.060 + 80 * 0.020
    read = {name: spec.load_reader(BENCH, name) for name in TRACED + COUNTED}
    assert read["kda_time_share"](run) == pytest.approx(
        (0.600 + 0.100 + 0.240 + 0.080 + 0.016) / whole * 100)
    assert read["moe_row_hit_share"](run) == pytest.approx(45.0)
    # 1000 real rows a tile: the bytes bind (the matrix products are a
    # fifth of their time at the matrix peak)
    floor = family.kda_scan_bytes(cfg, 1000.0, 2.0) / 819e9
    assert floor > family.kda_scan_flops(cfg, 1000.0) / 197e12
    assert read["kda_scan_roofline_share"](run) == pytest.approx(
        40 * floor / 0.600 * 100)
    # 28.5 slots live on average over the slice
    rows = live_rows(run, 100.0, 104.0)
    assert rows == pytest.approx(28.5)
    assert read["kda_step_roofline_share"](run) == pytest.approx(
        80 * family.kda_step_bytes(cfg, rows) / 819e9 / 0.240 * 100)
    for name in TRACED:
        assert 0 < read[name](run) < 100
        assert read[name](dict(run, traced=None)) is None
    assert read["moe_row_hit_share"](
        dict(run, counters={"t0": {"steps": 1}, "t1": {"steps": 2}})) is None
