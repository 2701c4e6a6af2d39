"""The `falcon_h1` family as the harness meets it (PR 44: new files and new
entries only): its configuration and mix load and map to the program, the
floors its readers divide by are the recurrence's own arithmetic, and the
four readers read a traced run's scopes and nothing where there are none
(the parent's program, an untraced run)."""
import pytest

from perfbench import metrics_lib as ml, scope_times, spec

BENCH = spec.load_benchmark()
CELL = "falcon-h1-34b.rag-answer"
NEW = ("ssd_scan_roofline_share", "ssd_step_roofline_share",
       "ssm_time_share", "hyb_attn_time_share")
STATE = 32 * 128 * 256 * 4            # one layer's float32 state of a slot


@pytest.fixture(scope="module")
def cfg():
    return spec.load_config(BENCH, "falcon-h1-34b")


def test_configuration_and_mix_load_and_map_to_the_program(cfg):
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b", "rag-answer", 1)
    family = spec.family_of(cfg)
    kw = family.model_kwargs(cfg)
    assert (kw["d_model"], kw["d_ff"], kw["n_heads"], kw["n_kv_heads"],
            kw["head_dim"], kw["vocab_size"], kw["n_layers"]) == (
        5120, 21504, 20, 4, 128, 261120, 6)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"],
            kw["ssm_groups"], kw["ssm_conv"]) == (32, 128, 256, 2, 4)
    assert kw["mixer_kinds"] == ["hyb"] * 6 and kw["scan_layers"] is False
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 72
    for key in ("stands_for", "assumed", "bytes", "reference_tolerance"):
        assert cfg[key], key
    mix = spec.load_traffic(BENCH, cell["traffic"])
    assert (mix["driver"], mix["clients"]) == ("closed", 16)
    # ISSUE 44's one retreat in the mix, taken: 2048-6144 as first written
    assert mix["prompt_len"] == {"dist": "uniform", "min": 2048, "max": 4096}
    assert mix["trace_s"] == 4.0
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 512}
    assert mix["reference_cases"] == [[512, 256], [2560, 256], [3840, 256]]
    engine = cfg["engine"]
    assert max(p + g for p, g in mix["reference_cases"]) \
        <= engine["max_len"] // 2
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= engine["max_len"]
    # a prefix cache or a draft is refused by the family as by the engine
    for bad in ({"prefix_cache_slots": 2}, {"spec": {"k": 2}}):
        with pytest.raises(spec.SpecError):
            family.model_kwargs(dict(cfg, engine=dict(engine, **bad)))


def test_the_floors_are_the_recurrences_arithmetic(cfg):
    family = spec.family_of(cfg)
    # one token of one layer beside the state: x in and y out (4096 each),
    # B and C (2 groups x 256 each) in bf16, dt in float32 a head
    row = 2 * 4096 * 2 + 2 * 2 * 256 * 2 + 4 * 32
    assert family.ssd_scan_bytes(cfg, 1024, 2.0) == 6 * (
        1024 * row + 2 * STATE)
    assert family.ssd_scan_flops(cfg, 1024) == 4 * 128 * 256 * 32 * 1024 * 6
    assert family.ssd_step_bytes(cfg, 16, 2.0) == 16 * 6 * 2 * 4_194_304 \
        + 16 * 6 * row
    # a decode step: the weights but the embedding, each live slot's state
    # and tail in and out, its live K and V
    idle = family.decode_step_bytes(cfg, [], 2.0, 2.0)
    assert idle == family.stored_param_bytes(cfg, 2.0) - 261120 * 5120 * 2
    one = family.decode_step_bytes(cfg, [4096.0], 2.0, 2.0) - idle
    assert one == 6 * (2 * (STATE + 3 * 5120 * 4) + 4096 * 2 * 4 * 128 * 2)
    assert 10.4e9 < family.stored_param_bytes(cfg, 2.0) < 10.6e9


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_an_entry_and_a_file(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "out_tok_s"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"][:24]}
    read = spec.load_reader(BENCH, name)
    assert read({"kind": "none"}) is None
    # a traced run of a program without the scopes (the parent's, another
    # family's): nothing to read, and no error
    assert read({"traced": (1.0, 5.0), "cell": "no-such-cell",
                 "config": spec.load_config(BENCH, "mistral-7b"),
                 "records": []}) is None


def test_the_readers_divide_what_they_say(cfg, monkeypatch):
    """A made-up traced slice: 5 tile steps of 80 ms and 20 decode steps of
    18 ms; the scopes' device times as `scope_times` would give them."""
    inside = {("ssd_scan", "jit_prefill"): (0.040, 5),
              ("ssd_step", "jit_decode"): (0.030, 20),
              ("ssd_step", "jit_prefill"): (0.0075, 5),
              ("hyb_ssm", "jit_prefill"): (0.090, 5),
              ("hyb_ssm", "jit_decode"): (0.070, 20),
              ("hyb_attn", "jit_prefill"): (0.030, 5),
              ("hyb_attn", "jit_decode"): (0.046, 20)}
    monkeypatch.setattr(scope_times, "scope_seconds",
                        lambda run, scope, program: inside.get(
                            (scope, program)))
    family = spec.family_of(cfg)
    run = {"traced": (100.0, 104.0), "cell": CELL, "config": cfg,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {
               "jit_prefill": {"durations_s": [0.080] * 5},
               "jit_decode": {"durations_s": [0.018] * 20}}},
           "counters": {"t0": {"prefill_dispatches": 10,
                               "prefill_tokens": 9000},
                        "t1": {"prefill_dispatches": 110,
                               "prefill_tokens": 99000}},
           # twelve requests decoding through the whole slice, one through
           # its first half, one that has only its first token
           "records": [{"arrivals": [90.0, 110.0]}] * 12
           + [{"arrivals": [98.0, 102.0]}, {"arrivals": [101.0]}]}
    assert ml.program_durations(run, "jit_decode") == [0.018] * 20
    whole = 5 * 0.080 + 20 * 0.018
    read = {name: spec.load_reader(BENCH, name) for name in NEW}
    assert read["ssm_time_share"](run) == pytest.approx(0.160 / whole * 100)
    assert read["hyb_attn_time_share"](run) == pytest.approx(
        0.076 / whole * 100)
    # 900 real tokens a tile: bandwidth binds (0.18 ms against 0.12 ms)
    floor = family.ssd_scan_bytes(cfg, 900.0, 2.0) / 819e9
    assert floor > family.ssd_scan_flops(cfg, 900.0) / 197e12
    assert read["ssd_scan_roofline_share"](run) == pytest.approx(
        5 * floor / 0.040 * 100)
    # 12.5 slots live on average over the slice
    floor = family.ssd_step_bytes(cfg, 12.5, 2.0) / 819e9
    assert read["ssd_step_roofline_share"](run) == pytest.approx(
        20 * floor / 0.030 * 100)
    for name in NEW:
        assert 0 < read[name](run) < 100
        assert read[name](dict(run, traced=None)) is None
