"""The `phi4flash` family as the harness meets it (PR 53: new files and new
entries only): its configuration and mix load and map to the program with
3,852.6 M parameters, the family's byte and FLOP counts are ISSUE 53's
arithmetic, and the seven readers read a traced run's scopes and counters
and nothing where there are none (the parent's program, an untraced run)."""
import json

import pytest

from perfbench import metrics_lib as ml, scope_times, spec

BENCH = spec.load_benchmark()
NAME = "phi-4-mini-flash-reasoning"
CELL = NAME + ".reason-longctx"
TRACED = ("s6_time_share", "s6_scan_roofline_share", "diff_time_share",
          "diff_attend_roofline_share", "diff_row_roofline_share")
COUNTED = ("xkv_streamed_per_live", "tail_rows_share")
ROW = 5120                            # K and V of a position: 20 heads of 64


@pytest.fixture(scope="module")
def cfg():
    return spec.load_config(BENCH, NAME)


def test_configuration_and_mix_load_and_map_to_the_program(cfg):
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "reason-longctx", 1)
    family = spec.family_of(cfg)
    kw = family.model_kwargs(cfg)
    assert (kw["d_model"], kw["d_ff"], kw["n_heads"], kw["n_kv_heads"],
            kw["head_dim"], kw["vocab_size"], kw["n_layers"], kw["window"],
            kw["win_ring"], kw["norm_eps"]) == (
        2560, 10240, 40, 20, 64, 200064, 32, 512, 1536, 1e-5)
    assert (kw["s6_inner"], kw["s6_state"], kw["s6_conv"],
            kw["s6_dt_rank"]) == (5120, 16, 4, 160)
    assert kw["mixer_kinds"] == ["s6", "win"] * 8 + ["s6", "att"] \
        + ["gmu", "xat"] * 7
    assert kw["diff_attn"] and kw["layer_norm"] and kw["tie_embeddings"] \
        and not kw["attn_rope"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    assert entry["reduced"] == [] and cfg["reduced"] == {}
    for key in ("stands_for", "assumed", "bytes", "deployment",
                "published", "reference_tolerance"):
        assert cfg[key], key
    mix = spec.load_traffic(BENCH, cell["traffic"])
    assert (mix["driver"], mix["clients"]) == ("closed", 16)
    assert mix["prompt_len"] in (
        {"dist": "uniform", "min": 4096, "max": 6144},
        {"dist": "uniform", "min": 3072, "max": 4608})     # the retreat
    assert mix["output_len"] == {"dist": "uniform", "min": 384, "max": 640}
    assert (mix["population"], mix["ramp_s"], mix["trace_s"],
            mix["population_seed"]) == (512, 30.0, 4.0, 53)
    assert mix["reference_cases"][:2] == [[512, 256], [2560, 256]]
    assert mix["reference_cases"][2] == [mix["prompt_len"]["max"], 256]
    engine = cfg["engine"]
    assert (engine["n_slots"], engine["max_len"], engine["prefill_chunk"],
            engine["prefill_budget"], engine["prefix_cache_slots"]) == (
        16, 12288, 512, 1024, 0)
    # serve_cell cuts a reference case's prompt to max_len // 2
    assert max(p for p, _ in mix["reference_cases"]) <= engine["max_len"] // 2
    with pytest.raises(spec.SpecError):
        family.model_kwargs(dict(cfg, engine=dict(engine,
                                                  prefix_cache_slots=2)))
    with pytest.raises(spec.SpecError):
        family.model_kwargs(dict(cfg, mb_per_layer=4))
    with pytest.raises(spec.SpecError):
        family.model_kwargs(dict(cfg, tie_word_embeddings=False))


def test_every_number_of_the_catalogs_config_is_in_the_file(cfg):
    """The catalog's row, where this checkout can see it: every key of its
    `config` is in the file under the same key and equal: nothing is
    reduced."""
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") \
                as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog here")
    (row,) = [r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning"]
    assert row["source_url"] == cfg["source"]
    assert {k for k, v in row["config"].items() if cfg[k] != v} == set()


def test_the_program_has_the_counted_parameters(cfg):
    """The program's own tree, on shapes: 3,852.6 M to 0.1 per cent, and
    the family's count to the parameter."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta
    family = spec.family_of(cfg)
    model = family.build_model(family.model_kwargs(cfg))
    tree = meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    n = sum(int(a.size) for a in jax.tree.leaves(tree))
    assert n == pytest.approx(3852.6e6, rel=1e-3)
    assert n == family.param_count(cfg)
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        family.weight_rule([p.key for p in path], a.shape)    # none unknown


def test_the_counts_are_the_parameter_arithmetic(cfg):
    family = spec.family_of(cfg)
    near = lambda x: pytest.approx(x, rel=1e-3)               # noqa: E731
    assert family.stored_param_bytes(cfg, 2.0) == near(7.705e9)
    assert family.kv_row_bytes(cfg, 2.0) == ROW
    assert family._layers(cfg) == {"s6": 9, "win": 8, "att": 1, "xat": 7,
                                   "gmu": 7}
    # a slot's nine states [5120, 16] and tails [3, 5120] in float32
    assert family._state_bytes(cfg) == 4 * 5120 * 16 + 4 * 3 * 5120
    assert family.s6_step_bytes(cfg, 16) == 9 * 16 * 2 * 389120
    assert family.s6_scan_flops(cfg, 1024) == 9 * 1024 * 5120 * 16 * 7
    assert family.s6_scan_bytes(cfg, 1024, 2.0) == 9 * (
        1024 * (5120 * (2 + 2 + 2 + 4) + 2 * 16 * 2) + 2 * 389120)
    # the recurrence's bytes take some fifty times its FLOPs' time at the
    # MATRIX peak: the share reads against the bandwidth
    assert (family.s6_scan_bytes(cfg, 1024, 2.0) / 819e9) \
        / (family.s6_scan_flops(cfg, 1024) / 197e12) > 15
    assert family.diff_attend_flops(cfg, 1000) == 1000 * 40 * 2 * (64 + 128)
    assert family.diff_attend_bytes(cfg, 5000, 2.0) == 5000 * ROW
    assert family.window_pairs(cfg, 0, 512) == 512 * 513 / 2
    assert family.window_pairs(cfg, 4096, 1024) == 1024 * 512
    # a decode step: the weights once (the table as the head), a live
    # slot's positions EIGHT times, its window's eight times, its nine
    # states and tails in and out
    idle = family.decode_step_bytes(cfg, [], 2.0, 2.0)
    assert idle == family.stored_param_bytes(cfg, 2.0)
    assert family.decode_step_bytes(cfg, [5400.0, 300.0], 2.0, 2.0) - idle \
        == 8 * 5700 * ROW + 8 * (512 + 300) * ROW + 2 * 9 * 2 * 389120
    assert family.diff_row_bytes(cfg, 16 * 5400, 16 * 512, 2.0) \
        == 8 * 16 * 5400 * ROW + 8 * 16 * 512 * ROW
    assert family.causal_attention_flops(cfg, 1, 4096, False) > 0
    assert family.train_step_flops(cfg, 1, 4096) > 6 * 3.85e9 * 4096


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
    assert {"out_tok_s", "ttft_mean_ms", "kv_pool_gb", "state_pool_gb",
            "hbm_peak_gb", "decode_roofline_share.tok",
            "tile_head_time_share"} <= listed
    # (both read a number for the cell; test_afmoe_family.py pins their
    # lists to the cell that brought them, which a `benchmark` PR may edit)
    assert not {"win_pool_gb", "win_streamed_per_live"} & listed
    assert not {n for n in listed if n.startswith((
        "att_", "dsa_", "blk_", "ssd_", "ssm_", "mla_", "moe_", "hyb_"))}


def test_the_readers_divide_what_they_say(cfg, monkeypatch):
    """A made-up traced slice: 20 tile steps of 90 ms and 100 decode steps
    of 22 ms; the scopes' device times as `scope_times` would give them."""
    inside = {("s6_scan", "jit_prefill"): (0.400, 20),
              ("s6_step", "jit_prefill"): (0.010, 20),
              ("s6_step", "jit_decode"): (0.050, 100),
              ("diff_attend", "jit_prefill"): (0.200, 20),
              ("diff_row", "jit_prefill"): (0.150, 20),
              ("diff_row", "jit_decode"): (0.800, 100)}
    monkeypatch.setattr(scope_times, "scope_seconds",
                        lambda run, scope, program: inside.get(
                            (scope, program)))
    family = spec.family_of(cfg)
    run = {"traced": (100.0, 104.0), "cell": CELL, "config": cfg,
           "mix": {"driver": "closed"}, "t_win0": 60.0, "t_win1": 105.0,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {
               "jit_prefill": {"durations_s": [0.090] * 20},
               "jit_decode": {"durations_s": [0.022] * 100}}},
           "counters": {"t0": {"prefill_dispatches": 10,
                               "prefill_tokens": 10000, "tile_rows": 10400,
                               "tail_rows_run": 170,
                               "xkv_rows_streamed": 0, "xkv_rows_live": 0},
                        "t1": {"prefill_dispatches": 110,
                               "prefill_tokens": 110000,
                               "tile_rows": 114400, "tail_rows_run": 1870,
                               "xkv_rows_streamed": 51200,
                               "xkv_rows_live": 40960}},
           # twelve requests decoding through the whole slice, one through
           # its first half, one that has only its first token
           "records": [{"arrivals": [90.0, 110.0], "prompt_len": 5000,
                        "sent": 70.0}] * 12
           + [{"arrivals": [98.0, 102.0], "prompt_len": 6000, "sent": 80.0},
              {"arrivals": [101.0], "prompt_len": 4096, "sent": 90.0}]}
    whole = 20 * 0.090 + 100 * 0.022
    read = {name: spec.load_reader(BENCH, name) for name in TRACED + COUNTED}
    assert read["s6_time_share"](run) == pytest.approx(0.460 / whole * 100)
    assert read["diff_time_share"](run) == pytest.approx(1.150 / whole * 100)
    assert read["xkv_streamed_per_live"](run) == 1.25
    assert read["tail_rows_share"](run) == pytest.approx(
        100 * 1700 / 104000)
    # 1000 real rows a tile; the bytes bind the scan
    floor = family.s6_scan_bytes(cfg, 1000.0, 2.0) / 819e9
    assert read["s6_scan_roofline_share"](run) == pytest.approx(
        20 * floor / 0.400 * 100)
    live = ml.mean_live_tokens(run, 100.0, 104.0)
    assert 12.5 * 5000 < live < 12.5 * 6100
    floor = family.diff_row_bytes(cfg, live, 12.5 * 512, 2.0) / 819e9
    assert read["diff_row_roofline_share"](run) == pytest.approx(
        100 * floor / 0.800 * 100)
    for name in TRACED:
        assert 0 < read[name](run) < 100, name
        assert read[name](dict(run, traced=None)) is None


def test_a_case_that_passes_any_limit_fails_whole(cfg):
    """`folded`: within every limit the gaps stand; past any one of the
    seven every token of the case counts as beyond the gap."""
    family = spec.family_of(cfg)
    tol = cfg["reference_tolerance"]
    keys = ("logit_rms", "first_rms", "edge_rms", "state_rel",
            "state_rel_last", "tail_rel", "tail_rel_last")
    limit = lambda k: tol["logit_rms" if k.endswith("_rms") else k]  # noqa
    sound = dict({k: 0.5 * limit(k) for k in keys}, gaps=[0.0, 0.02, 0.3])
    assert family.folded(sound, tol) == [0.0, 0.02, 0.3]
    for k in keys:
        got = family.folded(dict(sound, **{k: 2.0 * limit(k)}), tol)
        assert min(got) == pytest.approx(2.0 * tol["logit_gap"]), k
