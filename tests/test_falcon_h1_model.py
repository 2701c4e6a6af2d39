"""The model whose every block runs attention heads and a state-space mixer
(Mamba-2's SSD behind a short causal convolution) in parallel on one normed
input, against its plain reference
(perfbench/families/falcon_h1_reference.py: the only copy), on the CPU at a
small size in float32: hidden 64, 5 heads over 1 KV head of 16 (a group of
5), a mixer of 4 heads of 8 with a state of 16 in 2 groups, a convolution
of 4 taps, three layers, every multiplier as published, contexts to 96.

The chunked scan meets the recurrence wherever chunks and tiles fall (with
`dt A` down to -80 a token), a padded tail moves neither state nor tail,
three routes meet the reference on LOGITS (the one-shot forward, chunked
prefill then decode through caches laid out as the engine's pools, decode
rows riding a tile), prompts of 1 to 4 tokens (the tail's left padding),
the engine's greedy tokens are the reference's, a slot reused gives what a
fresh engine gives and holds its request's state and tail, the engine
refuses what does not carry the caches, each branch and each multiplier
matters, and each planted fault is caught.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import spec, weights
from perfbench.families import (falcon_h1, falcon_h1_controls,
                                falcon_h1_reference as ref)
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models import ssm
from ray_tpu.models.transformer import cache_dtype, cache_shapes
from tests.test_hybrid_mixer_model import cached_logits, cached_program

VOCAB = 257
with open(os.path.join(spec.ROOT, "perfbench", "configs",
                       "falcon-h1-34b.json")) as f:
    PUBLISHED = json.load(f)
MULTIPLIERS = ("attention_in_multiplier", "attention_out_multiplier",
               "embedding_multiplier", "key_multiplier",
               "lm_head_multiplier", "mlp_multipliers", "ssm_in_multiplier",
               "ssm_multipliers", "ssm_out_multiplier")


def config(**over) -> dict:
    """The family's configuration file at the small size: the published
    file with its widths cut, every multiplier and switch as published."""
    m = {k: v for k, v in PUBLISHED.items()
         if k not in ("engine", "reference_tolerance")}
    m.update(hidden_size=64, head_dim=16, num_attention_heads=5,
             num_key_value_heads=1, intermediate_size=96,
             num_hidden_layers=3, mamba_n_heads=4, mamba_d_head=8,
             mamba_d_ssm=32, mamba_d_state=16, mamba_n_groups=2,
             vocab_size=VOCAB, max_position_embeddings=512,
             param_dtype="float32")
    m.update(over)
    return m


def build(m: dict):
    kw = falcon_h1.model_kwargs(m)
    kw.update(dtype="float32", remat=False)
    return falcon_h1.build_model(kw)


def seeded(model, seed=0):
    """The family's seeded float32 weights; the norms' scales and D are
    drawn too, so that each matters."""
    params = weights.seeded_params(model, seed, falcon_h1.weight_rule)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(treedef, [
        a + 0.2 * jax.random.normal(jax.random.PRNGKey(100 + i), a.shape)
        if path[-1].key in ("scale", "norm_scale", "D") else a
        for i, (path, a) in enumerate(leaves)])


def tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         1, VOCAB))


@pytest.fixture(scope="module")
def small():
    """(config dict, model, params, reference logits of 90 tokens)"""
    m = config()
    model = build(m)
    params = seeded(model)
    return m, model, params, np.asarray(ref.logits(params, m, tokens(90)))


# -------------------------------------------------------- the recurrence
def inputs(T, H=4, P=8, G=2, N=16, seed=3):
    """x, dt, A, B, C, D of one sequence; dt A reaches -80 a token and
    stays above -1e-3 on other heads."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (1, T, H, P))
    dt = jax.nn.softplus(3.0 * jax.random.normal(ks[1], (1, T, H)))
    A = -jnp.asarray([1e-3, 0.3, 4.0, 80.0 / float(dt[..., 3].min())])
    B, C = (jax.random.normal(k, (1, T, G, N)) for k in ks[2:4])
    return x, dt, A, B, C, jax.random.normal(ks[4], (H,))


@pytest.mark.parametrize("chunk", [1, 5, 16, 128])
@pytest.mark.parametrize("tiles", [(48,), (16, 32), (7, 20, 21), (1,) * 48])
def test_ssd_scan_is_the_recurrence_whatever_the_split(tiles, chunk):
    x, dt, A, B, C, D = inputs(48)
    assert float((dt * A).min()) <= -80.0
    want, want_state = ref.ssd_with_state(x[0], dt[0], A, B[0], C[0], D)
    state = jnp.zeros((1, 4, 8, 16), jnp.float32)
    got, at = [], 0
    for n in tiles:
        cut = [a[:, at:at + n] for a in (x, dt, B, C)]
        y, state = (ssm.ssd_step if n == 1 else ssm.ssd_scan)(
            cut[0], cut[1], A, cut[2], cut[3], D, state,
            **({} if n == 1 else {"chunk": chunk}))
        got.append(y)
        at += n
    # float32 sums in another order; y reaches 30 here
    np.testing.assert_allclose(jnp.concatenate(got, 1)[0], want,
                               rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(state[0], want_state, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("n_real", [0, 1, 2, 13, 32])
def test_rows_no_request_owns_move_neither_state_nor_tail(n_real):
    """A tile's padded tail neither decays the state nor adds to it, and
    the convolution's tail handed on is the last three REAL rows."""
    x, dt, A, B, C, D = inputs(32)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (1, 4, 8, 16))
    real = (jnp.arange(32) < n_real)[None]
    y, padded = ssm.ssd_scan(x, dt, A, B, C, D, s0, real, chunk=8)
    if n_real:
        y_want, want = ssm.ssd_scan(
            *(a[:, :n_real] for a in (x, dt)), A,
            *(a[:, :n_real] for a in (B, C)), D, s0, chunk=8)
        np.testing.assert_allclose(y[:, :n_real], y_want, atol=2e-5)
    else:
        want = s0
    np.testing.assert_allclose(padded, want, atol=2e-5)
    rows = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 6))
    t0 = jax.random.normal(jax.random.PRNGKey(6), (1, 3, 6))
    w, b = jax.random.normal(jax.random.PRNGKey(7), (4, 6)), jnp.ones((6,))
    out, tail = ssm.causal_conv(rows, t0, w, b, real)
    np.testing.assert_array_equal(
        tail[0], jnp.concatenate([t0[0], rows[0, :n_real]])[-3:])
    np.testing.assert_allclose(
        out[0, :n_real], ref.conv_taps(jnp.concatenate(
            [t0[0], rows[0]]), w, b)[3:3 + n_real], atol=1e-6)
    # one row a slot, some of them no request's
    live = jnp.asarray([True, False])
    s2 = jnp.concatenate([s0, s0 + 1.0])
    two = lambda a: jnp.concatenate([a[:, :1], a[:, 1:2]])    # noqa: E731
    _, new = ssm.ssd_step(two(x), two(dt), A, two(B), two(C), D, s2, live)
    np.testing.assert_array_equal(new[1], s2[1])
    assert float(jnp.abs(new[0] - s2[0]).max()) > 1e-3


# ------------------------------------------------ three routes, on logits
def test_one_shot_forward_matches_reference(small):
    m, model, params, want = small
    got = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(tokens(90))[None])[0]
    # float32 against float32: the chunked scan's and the flash kernel's
    # sums in another order, through three layers, on logits of spread 1.2
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert 0.5 < float(np.std(want)) < 3.0


@pytest.mark.parametrize("tile", [8, 16, 32])
def test_prefill_then_decode_through_pools_matches_reference(small, tile):
    """Prompts of several lengths (one ends on a tile's edge, one a row
    past it, one inside a tile), each prefilled in several tiles, then
    decoded side by side: every logit row is the reference's full
    forward's."""
    m, model, params, want = small
    seq = tokens(90)
    prompt_lens = (64, 65, 41)
    got = cached_logits(model, params, [seq] * 3, prompt_lens, tile, 96)
    for g, n in zip(got, prompt_lens):
        np.testing.assert_allclose(g, want[n - 1:n - 1 + len(g)], atol=1e-4)


@pytest.mark.parametrize("n", [1, 2, 3, 4])
def test_a_prompt_shorter_than_the_convolution(small, n):
    """The tail of a prompt of fewer rows than the convolution's taps is
    left-padded with zeros, as a fresh sequence is."""
    m, model, params, want = small
    got, = cached_logits(model, params, [tokens(90)], (n,), 8, 96)
    np.testing.assert_allclose(got[:20], want[n - 1:n + 19], atol=1e-4)


def test_decode_rows_riding_a_tile_equal_decode_alone(small):
    m, model, params, want = small
    seq = tokens(90)
    alone = cached_logits(model, params, [seq] * 2, (64, 41), 16, 96)
    riding = cached_logits(model, params, [seq] * 2, (64, 41), 16, 96,
                           ride=True)
    for a, r, n in zip(alone, riding, (64, 41)):
        np.testing.assert_allclose(r, a, atol=2e-5)
        np.testing.assert_allclose(r, want[n - 1:n - 1 + len(r)], atol=1e-4)


# ---------------------------------------------------------------- engine
ENGINE = dict(n_slots=2, max_len=96, prefill_chunk=8, prefill_budget=16)


def run_engine(model, params, prompts, n_new, **over):
    eng = InferenceEngine(model, params,
                          EngineConfig(**dict(ENGINE, **over)))
    hs = [eng.submit(np.asarray(p), max_new_tokens=n_new) for p in prompts]
    while eng.sched.has_work():
        eng.step()
    return eng, [list(h) for h in hs]


def test_engine_tokens_are_the_references_and_counters_count(small):
    """Three prompts through two slots (the later ones' tiles carry the
    first's decode rows, one slot is reused): every served token is the
    reference's argmax at its position."""
    m, model, params, _ = small
    prompts = [tokens(70, seed=4), tokens(37, seed=5), tokens(3, seed=6)]
    eng, served = run_engine(model, params, prompts, 12)
    for p, s in zip(prompts, served):
        assert max(ref.teacher_forced_gaps(params, m, list(p), s)) == 0.0
    st = eng.stats()
    assert eng.decode_compile_count == 1 and st["fused_steps"] > 0
    assert st["state_pool_bytes"] == 3 * 2 * 4 * 8 * 16 * 4
    assert st["conv_pool_bytes"] == 3 * 2 * 3 * (32 + 2 * 2 * 16) * 4
    assert st["kv_pool_bytes"] == st["state_pool_bytes"] \
        + st["conv_pool_bytes"] + 4 * (2 * 3 * 2 * 96 * 1 * 16)


def test_a_slot_reused_holds_its_requests_state_and_tail(small):
    """The slot's last owner leaves K, V, a state and a tail behind: the
    next request's are its own, after `insert` and to its last token."""
    m, model, params, _ = small
    a, b = tokens(60, seed=6), tokens(45, seed=7)
    eng = InferenceEngine(model, params,
                          EngineConfig(**dict(ENGINE, n_slots=1)))
    served = []
    for p in (a, b):
        h = eng.submit(np.asarray(p), max_new_tokens=10)
        while eng.sched.has_work():
            eng.step()
        served.append(list(h))
    _, fresh = run_engine(model, params, [b], 10, n_slots=1)
    assert served[1] == fresh[0]
    want = ref.teacher_forced_gaps(params, m, list(b), served[1],
                                   with_rows=True)
    # the pool after the request's last decode row (its last token is
    # sampled and never fed): the reference's after the same tokens
    np.testing.assert_allclose(eng._slots.s[:, 0],
                               jnp.stack(want["states"])[:, 1],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(eng._slots.c[:, 0],
                               jnp.stack(want["tails"])[:, 1],
                               rtol=1e-4, atol=1e-5)


def test_the_tile_program_unembeds_the_rows_it_samples(small):
    """Every block's two branches, like every other model's tile
    (tests/test_fused_step.py): the head runs over the prompt's row and
    the two riding rows, never over the tile."""
    from tests.test_fused_step import tile_head_rows
    _, model, params, _ = small
    eng = InferenceEngine(model, params, EngineConfig(**ENGINE))
    assert tile_head_rows(eng) == ([1 + ENGINE["n_slots"]], 16)


def test_engine_refuses_what_does_not_carry_the_caches(small):
    m, model, params, _ = small
    with pytest.raises(ValueError, match="beyond K and V"):
        InferenceEngine(model, params, EngineConfig(
            **dict(ENGINE, prefix_cache_slots=1)))
    ok = {"max_len": 480, "prefill_budget": 32}
    falcon_h1.model_kwargs(config(engine=ok))
    for bad, why in (({"prefix_cache_slots": 1}, "prefix_cache_slots"),
                     ({"spec": {"k": 2}}, "spec absent"),
                     ({"max_len": 96}, "whole blocks")):
        with pytest.raises(spec.SpecError, match=why):
            falcon_h1.model_kwargs(config(engine=dict(ok, **bad)))


# ------------------------------------------- each part of the sum matters
def off_reference(model, params, want):
    got = cached_logits(model, params, [tokens(90)] * 2, (64, 41), 16, 96)
    return max(float(np.abs(g - want[n - 1:n - 1 + len(g)]).max())
               for g, n in zip(got, (64, 41)))


ONES = {key: [1.0] * len(PUBLISHED[key])
        if isinstance(PUBLISHED[key], list) else 1.0
        for key in MULTIPLIERS if PUBLISHED[key] != 1}
PARTS = [(key, i) for key, one in ONES.items()
         for i in (range(len(one)) if isinstance(one, list) else [None])]


@pytest.mark.parametrize("key,i", PARTS, ids=[
    key if i is None else f"{key}.{i}" for key, i in PARTS])
def test_a_multiplier_left_at_1_is_not_the_model(small, key, i):
    """The program with one published multiplier at 1 is off the
    reference (which has them all) by far more than rounding."""
    m, _, params, want = small
    value = 1.0 if i is None else [
        1.0 if j == i else v for j, v in enumerate(m[key])]
    assert off_reference(build(config(**{key: value})), params, want) > 1e-2


# ------------------------------------------------------------ the controls
@pytest.mark.parametrize("control", list(falcon_h1_controls.CONTROLS))
def test_planted_fault_is_caught(small, control):
    """Each control of the cell, planted at the small size in the path
    through tiles, pools and decode rows, moves the logits off the
    reference's; the sound program does not."""
    m, model, params, want = small
    cached_program.cache_clear()        # a planted function is traced anew
    try:
        with falcon_h1_controls.planted(control, model, params) as (mm, pp):
            off = off_reference(mm, pp, want)
    finally:
        cached_program.cache_clear()
    assert (off < 1e-4) == (control == "sound"), off


def run_cfg(m, **tolerance):
    """The small configuration as a run of the cell holds it."""
    return dict(m, engine=dict(ENGINE, max_len=480, prefill_budget=32,
                               max_ongoing_requests=8),
                family="falcon_h1", _family_file=falcon_h1.__file__,
                reference_tolerance=dict(
                    {"logit_gap": 1e-3, "share_within": 1.0,
                     "logit_rms": 1e-4, "state_rel": 1e-4,
                     "tail_rel": 1e-5}, **tolerance))


@pytest.mark.parametrize("control,number", [
    ("sound", None), ("state_of_last_owner_left", "state_rel"),
    ("state_in_bf16", "state_rel"), ("tail_from_padded_rows", "tail_rel"),
    ("state_zeroed_at_tile_start", "edge_rms"),
    ("tail_zeroed_at_tile_start", "edge_rms")])
def test_controls_are_judged_as_a_run_is(small, control, number):
    """The tool that reads the controls on the chip, at the small size:
    slots that have had an owner, the cases in flight together, the
    program's logits, state and tail taken with the fault planted, the
    family's numbers folded as a run folds them (here in float32 the sound
    program leaves every token at a gap of 0). Each fault fails by the
    number named: a last owner's state right after `insert`, a tail taken
    from padded rows there too, a tail or a state lost between two tiles
    at the rows that open the next tile."""
    from perfbench.families.minicpm_sala_controls import serve
    m, model, params, _ = small
    cfg = run_cfg(m)
    cases = [(tokens(70, seed=11).tolist(), 24),
             (tokens(33, seed=12).tolist(), 24)]
    falcon_h1._programs.cache_clear()    # a planted function is traced
    try:
        with falcon_h1_controls.planted(control, model, params) as (mm, pp):
            served = serve(mm, pp, cfg, cases, 0, warm=(40, 4))
            rows = [falcon_h1.program_rows(pp, cfg, p, g, model=mm)
                    for (p, _), g in zip(cases, served)]
    finally:
        falcon_h1._programs.cache_clear()
    row = falcon_h1_controls.judge(cfg, params, cases, served, rows)
    assert row["passes"] == (control == "sound"), row["beyond"]
    if number:
        assert max(row[number + "_by_case"]) > row[
            number.replace("edge", "logit") + "_limit"]
        assert row["beyond"] == row["n_tokens"]


def test_the_harness_call_reads_every_number(small):
    """replica.bench_reference's call: the family builds the program from
    the configuration (bf16 activations, as served), takes its logits,
    state and tail on the served tokens and folds their distances from the
    reference's into the gaps: within limits above bf16's own rounding
    nothing changes, under a limit below it every token counts as
    beyond."""
    m, model, params, _ = small
    prompt = tokens(40, seed=13).tolist()
    _, (served,) = run_engine(model, params, [prompt], 12)
    plain = ref.teacher_forced_gaps(params, m, prompt, served, pad_to=128)
    loose = dict(logit_rms=1.0, state_rel=1.0, tail_rel=1.0)
    falcon_h1._programs.cache_clear()
    try:
        gaps, spread = falcon_h1.teacher_forced_gaps(
            params, run_cfg(m, **loose), prompt, served, pad_to=128,
            with_spread=True)
        assert gaps == plain and spread > 0
        score = falcon_h1.scored(params, run_cfg(m), prompt, served, 128)
        assert len(score["logit_rms_each"]) == 12
        assert np.shape(score["state_rel"]) == (4,)
        assert 1e-4 < score["logit_rms"] < 1.0              # bf16's rounding
        assert 1e-4 < falcon_h1.state_number(score["state_rel"]) < 0.05
        assert 1e-4 < score["tail_rel"] < 0.02
        assert 1e-4 < score["edge_rms"] < 1.0   # a prompt of three tiles
        for key in loose:
            tight = falcon_h1.teacher_forced_gaps(
                params, run_cfg(m, **dict(loose, **{
                    key: (score[key] if key != "state_rel" else
                          falcon_h1.state_number(score[key])) / 2})),
                prompt, served, pad_to=128)
            assert min(tight) == pytest.approx(2e-3)
    finally:
        falcon_h1._programs.cache_clear()


# ------------------------------------------------------ family and config
def test_family_seeds_every_leaf_and_counts_the_new_mathematics(small):
    m, model, params, _ = small
    shapes = cache_shapes(model.cfg, 2, 96)
    assert shapes == {"k": (3, 2, 96, 1, 16), "v": (3, 2, 96, 1, 16),
                      "s": (3, 2, 4, 8, 16), "c": (3, 2, 3, 96)}
    assert list(shapes) == ["k", "v", "s", "c"]
    assert cache_dtype("s", jnp.bfloat16) == cache_dtype(
        "c", jnp.bfloat16) == jnp.float32
    n = sum(a.size for a in jax.tree.leaves(params)
            if a.ndim > 1) - 4 * 96 * 3          # the convolutions' taps
    assert falcon_h1.stored_param_bytes(m, 1.0) == n
    # a step moves the weights, each live slot's state and tail in and
    # out, its live K and V
    base = falcon_h1.decode_step_bytes(m, [], 4.0, 4.0)
    assert base == 4.0 * (n - VOCAB * 64)
    more = falcon_h1.decode_step_bytes(m, [90.0, 10.0], 4.0, 4.0)
    assert more - base == 3 * (2 * 2 * (4 * 8 * 16 + 3 * 96) * 4.0
                               + 100 * 2 * 16 * 4.0)
    assert falcon_h1.ssd_scan_flops(m, 10) == 4 * 8 * 16 * 4 * 10 * 3


def test_shipped_configuration_states_the_published_widths():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "falcon-h1-34b")
    kw = falcon_h1.model_kwargs(cfg)
    assert (kw["d_model"], kw["d_ff"], kw["n_heads"], kw["n_kv_heads"],
            kw["head_dim"], kw["vocab_size"]) == (5120, 21504, 20, 4, 128,
                                                  261120)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"],
            kw["ssm_groups"], kw["ssm_conv"]) == (32, 128, 256, 2, 4)
    assert kw["mixer_kinds"] == ["hyb"] * 6 == ["hyb"] * kw["n_layers"]
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 72
    assert (kw["scale_emb"], kw["logit_scale"], kw["attn_out_mult"],
            kw["key_mult"], kw["ssm_out_mult"]) == (
        5.656854249492381, 0.0078125, 0.0375, 0.011048543456039804,
        0.08838834764831845)
    assert kw["ssm_mults"] == [0.3535533905932738, 0.25, 0.1767766952966369,
                               0.5, 0.3535533905932738]
    assert kw["mlp_mults"] == [0.1767766952966369, 0.011160714285714284]
    with open(os.path.join(spec.ROOT, "perfbench", "traffic",
                           "rag-answer.json")) as f:
        mix = json.load(f)
    assert mix["clients"] == cfg["engine"]["n_slots"] == 16
    model = falcon_h1.build_model(kw)
    shapes = cache_shapes(model.cfg, 16, cfg["engine"]["max_len"])
    nbytes = {n: int(np.prod(s)) * (4 if n in "sc" else 2)
              for n, s in shapes.items()}
    assert nbytes["s"] == 6 * 16 * 32 * 128 * 256 * 4
    assert nbytes["c"] == 6 * 16 * 3 * 5120 * 4
    assert nbytes["k"] == 6 * 16 * 8192 * 4 * 128 * 2
