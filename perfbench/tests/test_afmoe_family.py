"""The `afmoe` family as the harness meets it (PR 46: new files and new
entries only): its configuration and mix load and map to the program, the
floors its readers divide by are the window's own arithmetic, and the seven
readers read a traced run's scopes and counters and nothing where there are
none (the parent's program, an untraced run)."""
import pytest

from perfbench import metrics_lib as ml, scope_times, spec

BENCH = spec.load_benchmark()
CELL = "trinity-large-preview.longdoc-report"
TRACED = ("win_attend_roofline_share", "win_row_roofline_share",
          "win_time_share", "att_time_share", "moe_time_share")
COUNTED = ("win_pool_gb", "win_streamed_per_live")
KV_ROW = 2 * 8 * 128 * 2              # K and V of one position of one layer


@pytest.fixture(scope="module")
def cfg():
    return spec.load_config(BENCH, "trinity-large-preview")


def test_configuration_and_mix_load_and_map_to_the_program(cfg):
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-preview", "longdoc-report", 1)
    family = spec.family_of(cfg)
    kw = family.model_kwargs(cfg)
    assert (kw["d_model"], kw["d_ff"], kw["expert_d_ff"], kw["n_heads"],
            kw["n_kv_heads"], kw["head_dim"], kw["vocab_size"],
            kw["n_layers"]) == (3072, 12288, 3072, 48, 8, 128, 25024, 5)
    assert kw["mixer_kinds"] == ["win", "win", "att", "win", "win"]
    assert (kw["window"], kw["win_ring"], kw["n_dense_layers"]) == (
        4096, 5120, 1)
    assert (kw["n_experts"], kw["expert_top_k"], kw["experts_held"],
            kw["n_shared_experts"], kw["router"], kw["route_scale"]) == (
        256, 4, [96, 32], 1, "sigmoid", 2.448)
    assert kw["scale_emb"] == 3072 ** 0.5 and kw["attn_rope"] is False
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 60
    (entry,) = [c for c in BENCH["configs"]
                if c["name"] == "trinity-large-preview"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key in ("stands_for", "assumed", "bytes", "deployment",
                "published", "reference_tolerance"):
        assert cfg[key], key
    mix = spec.load_traffic(BENCH, cell["traffic"])
    assert (mix["driver"], mix["clients"]) == ("closed", 16)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 12288,
                                 "max": 18432}
    assert mix["output_len"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert (mix["ramp_s"], mix["trace_s"], mix["population_seed"]) == (
        30.0, 4.0, 46)
    assert mix["reference_cases"] == [[1024, 256], [6144, 256], [12288, 256]]
    engine = cfg["engine"]
    assert max(p + g for p, g in mix["reference_cases"]) \
        <= engine["max_len"] // 2
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= engine["max_len"]
    # a prefix cache or a draft is refused by the family as by the engine
    for bad in ({"prefix_cache_slots": 2}, {"spec": {"k": 2}}):
        with pytest.raises(spec.SpecError):
            family.model_kwargs(dict(cfg, engine=dict(engine, **bad)))


def test_the_floors_are_the_windows_arithmetic(cfg):
    family = spec.family_of(cfg)
    # 8.64 GB of weights: ISSUE 46's reckoning
    assert 8.63e9 < family.stored_param_bytes(cfg, 2.0) < 8.66e9
    # a row at position t attends min(t + 1, 4096) keys
    assert family.window_pairs(cfg, 0, 4096) == 4096 * 4097 / 2
    assert family.window_pairs(cfg, 0, 5000) == 4096 * 4097 / 2 + 904 * 4096
    assert family.window_pairs(cfg, 8192, 1024) == 1024 * 4096
    assert family.window_pairs(cfg, 3584, 1024) == sum(
        min(t + 1, 4096) for t in range(3584, 4608))
    # four sliding layers: QK^T and AV, 2 FLOP each, 48 heads of 128
    assert family.win_attend_flops(cfg, 1024 * 4096) \
        == 4 * 4 * 1024 * 4096 * 48 * 128
    assert family.win_attend_bytes(cfg, 1024, 5119, 2.0) == 4 * (
        5119 * KV_ROW + 2 * 1024 * 48 * 128 * 2)
    assert family.win_row_bytes(cfg, 16 * 4096, 2.0) \
        == 4 * 16 * 4096 * KV_ROW
    # a decode step: the weights but the embedding; a live slot's every
    # position of the full layer and 4,096 of each sliding one
    idle = family.decode_step_bytes(cfg, [], 2.0, 2.0)
    assert idle == family.stored_param_bytes(cfg, 2.0) - 25024 * 3072 * 2
    one = family.decode_step_bytes(cfg, [20000.0], 2.0, 2.0) - idle
    assert one == KV_ROW * (20000 + 4 * 4096)


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


def test_the_readers_divide_what_they_say(cfg, monkeypatch):
    """A made-up traced slice: 40 tile steps of 30 ms and 60 decode steps
    of 14 ms; the scopes' device times as `scope_times` would give them."""
    inside = {("win_attend", "jit_prefill"): (0.400, 40),
              ("win_row", "jit_prefill"): (0.080, 40),
              ("win_row", "jit_decode"): (0.120, 60),
              ("att_attend", "jit_prefill"): (0.200, 40),
              ("att_row", "jit_prefill"): (0.040, 40),
              ("att_row", "jit_decode"): (0.060, 60),
              ("moe_router", "jit_prefill"): (0.010, 40),
              ("moe_shared", "jit_decode"): (0.020, 60),
              ("moe_experts", "jit_prefill"): (0.100, 40),
              ("moe_experts", "jit_decode"): (0.300, 60)}
    monkeypatch.setattr(scope_times, "scope_seconds",
                        lambda run, scope, program: inside.get(
                            (scope, program)))
    family = spec.family_of(cfg)
    run = {"traced": (100.0, 104.0), "cell": CELL, "config": cfg,
           "mix": {"driver": "closed"}, "t_win0": 60.0, "t_win1": 105.0,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {
               "jit_prefill": {"durations_s": [0.030] * 40},
               "jit_decode": {"durations_s": [0.014] * 60}}},
           "counters": {"t0": {"prefill_dispatches": 10,
                               "prefill_tokens": 10000,
                               "win_rows_streamed": 0, "win_rows_live": 0},
                        "t1": {"prefill_dispatches": 110,
                               "prefill_tokens": 110000,
                               "win_rows_streamed": 51200,
                               "win_rows_live": 40960,
                               "win_pool_bytes": 1342177280}},
           # twelve requests decoding through the whole slice, one through
           # its first half, one that has only its first token
           "records": [{"arrivals": [90.0, 110.0], "prompt_len": 16384,
                        "sent": 70.0}] * 12
           + [{"arrivals": [98.0, 102.0], "prompt_len": 20000, "sent": 80.0},
              {"arrivals": [101.0], "prompt_len": 12288, "sent": 90.0}]}
    assert ml.program_durations(run, "jit_decode") == [0.014] * 60
    whole = 40 * 0.030 + 60 * 0.014
    read = {name: spec.load_reader(BENCH, name) for name in TRACED + COUNTED}
    assert read["win_time_share"](run) == pytest.approx(0.600 / whole * 100)
    assert read["att_time_share"](run) == pytest.approx(0.300 / whole * 100)
    assert read["moe_time_share"](run) == pytest.approx(0.430 / whole * 100)
    assert read["win_pool_gb"](run) == pytest.approx(1.34217728)
    assert read["win_streamed_per_live"](run) == 1.25
    # 1000 real rows a tile; a 16,384 prompt's rows attend 3,584.1 keys on
    # average, and the FLOPs bind (2.1 ms against 0.2 ms)
    prompts = [16384] * 12 + [20000, 12288]
    pairs = 1000.0 * sum(family.window_pairs(cfg, 0, p)
                         for p in prompts) / sum(prompts)
    floor = family.win_attend_flops(cfg, pairs) / 197e12
    assert floor > family.win_attend_bytes(cfg, 1000.0, 5095, 2.0) / 819e9
    assert read["win_attend_roofline_share"](run) == pytest.approx(
        40 * floor / 0.400 * 100)
    # 12.5 slots live on average over the slice, each past the window
    floor = family.win_row_bytes(cfg, 12.5 * 4096, 2.0) / 819e9
    assert read["win_row_roofline_share"](run) == pytest.approx(
        60 * floor / 0.120 * 100)
    for name in TRACED:
        assert 0 < read[name](run) < 100
        assert read[name](dict(run, traced=None)) is None
