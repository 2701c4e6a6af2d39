"""The decoder whose second half keeps no cache (Phi-4-mini-flash's "SambaY":
Mamba-1 layers, differential attention over a window's ring and over ONE
cache by position that the cross layers read, gated memory units) against
its plain reference (perfbench/families/phi4flash_reference.py: the only
copy), on the CPU at a small size in float32: hidden 64, 8 query heads over
4 KV heads of 8 (4 pairs over 2), a mixer of 128 channels with a state of 4
and a step through rank 4, a window of 8 in a ring of 32, twelve layers by
the published rule (three ["s6", "win"] pairs, "s6", "att", two ["gmu",
"xat"] pairs), contexts to 96.

The scan meets the recurrence wherever tiles and chunks fall, a padded tail
moves neither state nor tail, the pair form is two plain softmaxes, three
routes meet the reference on LOGITS (the one-shot forward, tiles then rows
through caches laid out as the engine's pools, decode rows riding a tile),
the rows a call names pass the cacheless layers alone and give what every
row gives, the engine's greedy tokens are the reference's and its counters
count, a slot reused inherits nothing, and each planted fault is caught.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import spec, weights
from perfbench.families import (phi4flash, phi4flash_controls,
                                phi4flash_reference as ref)
from ray_tpu.inference import kv_cache
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models import diff_attention as da, sparse_attention as sa, ssm
from ray_tpu.models.transformer import (cache_dtype, cache_shapes,
                                        cacheless_tail, decode_rows_read)
from tests.test_hybrid_mixer_model import cached_logits, tokens

VOCAB = 257
with open(os.path.join(spec.ROOT, "perfbench", "configs",
                       "phi-4-mini-flash-reasoning.json")) as f:
    PUBLISHED = json.load(f)
ENGINE = dict(n_slots=2, max_len=96, prefill_chunk=8, prefill_budget=16)
KINDS = ("s6", "win") * 3 + ("s6", "att") + ("gmu", "xat") * 2


def config(**over) -> dict:
    """The family's configuration file at the small size: the published
    file with its widths and depth cut."""
    m = {k: v for k, v in PUBLISHED.items() if k != "reference_tolerance"}
    m.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
             intermediate_size=96, num_hidden_layers=12, sliding_window=8,
             vocab_size=VOCAB, max_position_embeddings=512,
             param_dtype="float32", engine=dict(ENGINE),
             assumed_sizes=dict(d_state=4, d_conv=4, expand=2, dt_rank=4,
                                win_ring=32))
    m.update(over)
    return m


def build(m: dict):
    kw = phi4flash.model_kwargs(m)
    kw.update(dtype="float32", remat=False)
    return phi4flash.build_model(kw)


def seeded(model, seed=0):
    """The family's seeded float32 weights; the norms' scales and D are
    drawn too, so that each matters."""
    params = weights.seeded_params(model, seed, phi4flash.weight_rule)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(treedef, [
        a + 0.2 * jax.random.normal(jax.random.PRNGKey(100 + i), a.shape)
        if path[-1].key in ("scale", "subln", "D") else a
        for i, (path, a) in enumerate(leaves)])


@pytest.fixture(scope="module")
def small():
    """(config dict, model, params, reference logits of 90 tokens)"""
    m = config()
    model = build(m)
    params = seeded(model)
    return m, model, params, np.asarray(ref.logits(params, m, tokens(90)))


def test_the_arrangement_is_the_published_rule():
    assert tuple(ref.kinds(12)) == KINDS == build(config()).cfg.mixer_kinds
    full = ref.kinds(32)
    assert full[:18] == ["s6", "win"] * 8 + ["s6", "att"]
    assert full[18:] == ["gmu", "xat"] * 7
    assert cacheless_tail(build(config()).cfg) == 8


# -------------------------------------------------------- the recurrence
def inputs(T, I=24, N=4, seed=3):
    """x, dt, A, B, C, D of one sequence; dt A reaches -80 a row and stays
    above -1e-3 elsewhere."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (1, T, I))
    dt = jax.nn.softplus(3.0 * jax.random.normal(ks[1], (1, T, I)))
    A = -jnp.exp(jnp.linspace(-7.0, 4.0, N * I).reshape(N, I))
    B, C = (jax.random.normal(k, (1, T, N)) for k in ks[2:4])
    return x, dt, A, B, C, jax.random.normal(ks[4], (I,))


@pytest.mark.parametrize("chunk", [1, 5, 16])
@pytest.mark.parametrize("tiles", [(48,), (16, 32), (7, 20, 21), (1,) * 48])
def test_s6_scan_is_the_recurrence_whatever_the_split(tiles, chunk):
    x, dt, A, B, C, D = inputs(48)
    assert float((dt[..., None, :] * A).min()) <= -80.0
    want, want_state = ref.s6_with_state(
        x[0], dt[0], A.T, B[0], C[0], D, jnp.asarray([48]))
    state = jnp.zeros((1, 4, 24), jnp.float32)
    got, at = [], 0
    for n in tiles:
        cut = [a[:, at:at + n] for a in (x, dt, B, C)]
        y, state = (ssm.s6_step if n == 1 else ssm.s6_scan)(
            cut[0], cut[1], A, cut[2], cut[3], D, state,
            **({} if n == 1 else {"chunk": chunk}))
        got.append(y)
        at += n
    np.testing.assert_allclose(jnp.concatenate(got, 1)[0], want,
                               rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(state[0], want_state[0].T, rtol=2e-5,
                               atol=2e-4)


def test_two_tiles_are_one_scan_and_the_steps_row_by_row():
    """In float32 the three forms do the same operations on the same
    numbers in the same order (each program's compiler may fuse a
    multiply-add: a last bit)."""
    x, dt, A, B, C, D = inputs(40)
    zero = jnp.zeros((1, 4, 24), jnp.float32)
    one, s_one = ssm.s6_scan(x, dt, A, B, C, D, zero)
    cut = lambda a, lo, hi: a[:, lo:hi]                      # noqa: E731
    a, s = ssm.s6_scan(*(cut(v, 0, 16) for v in (x, dt)), A,
                       *(cut(v, 0, 16) for v in (B, C)), D, zero)
    b, s = ssm.s6_scan(*(cut(v, 16, 40) for v in (x, dt)), A,
                       *(cut(v, 16, 40) for v in (B, C)), D, s)
    np.testing.assert_allclose(jnp.concatenate([a, b], 1), one, rtol=2e-6,
                               atol=2e-5)
    np.testing.assert_allclose(s, s_one, rtol=2e-6, atol=2e-5)
    rows, s = [], zero
    for t in range(40):
        y, s = ssm.s6_step(*(cut(v, t, t + 1) for v in (x, dt)), A,
                           *(cut(v, t, t + 1) for v in (B, C)), D, s)
        rows.append(y)
    np.testing.assert_allclose(jnp.concatenate(rows, 1), one, rtol=2e-6,
                               atol=2e-5)
    np.testing.assert_allclose(s, s_one, rtol=2e-6, atol=2e-5)


@pytest.mark.parametrize("n_real", [0, 1, 2, 13, 32])
def test_rows_no_request_owns_move_neither_state_nor_tail(n_real):
    """A tile's padded tail neither decays the state nor adds to it, and
    the convolution's tail handed on is the last three REAL rows."""
    x, dt, A, B, C, D = inputs(32)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (1, 4, 24))
    real = (jnp.arange(32) < n_real)[None]
    y, padded = ssm.s6_scan(x, dt, A, B, C, D, s0, real, chunk=8)
    if n_real:
        y_want, want = ssm.s6_scan(
            *(a[:, :n_real] for a in (x, dt)), A,
            *(a[:, :n_real] for a in (B, C)), D, s0, chunk=8)
        np.testing.assert_allclose(y[:, :n_real], y_want, atol=2e-5)
    else:
        want = s0
        np.testing.assert_array_equal(padded, s0)
    np.testing.assert_allclose(padded, want, rtol=1e-6, atol=1e-6)
    # a dead slot's row leaves its state as it was
    _, kept = ssm.s6_step(x[:, :1], dt[:, :1], A, B[:, :1], C[:, :1], D, s0,
                          jnp.asarray([False]))
    np.testing.assert_array_equal(kept, s0)
    rows = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 6))
    t0 = jax.random.normal(jax.random.PRNGKey(6), (1, 3, 6))
    w = jax.random.normal(jax.random.PRNGKey(7), (4, 6))
    _, tail = ssm.causal_conv(rows, t0, w, jnp.zeros((6,)), real)
    want = jnp.concatenate([t0, rows[:, :n_real]], 1)[:, -3:]
    np.testing.assert_array_equal(tail, want)


# -------------------------------------------------------- the pair form
@pytest.mark.parametrize("window", [0, 8])
def test_the_pair_form_is_two_plain_softmaxes(window):
    """Padded queries against K and V kept by pair, through ordinary
    grouped-query attention, then `combine`: the reference's two masked
    softmaxes a pair, their difference, the norm, the scale."""
    L, H, Hkv, d = 24, 8, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (1, L, H, d))
    k = jax.random.normal(ks[1], (1, L, Hkv, d))
    v = jax.random.normal(ks[2], (1, L, Hkv, d))
    scale = 1.0 + 0.2 * jax.random.normal(ks[3], (2 * d,))
    lam, lam0 = 0.37, 0.55
    at = jnp.arange(L)
    mask = at[None, :] <= at[:, None]
    if window:
        mask &= at[None, :] > at[:, None] - window
    out = sa.masked_attention(
        da.paired(q), k.reshape(1, L, Hkv // 2, 2 * d),
        v.reshape(1, L, Hkv // 2, 2 * d), mask[None])
    got = da.combine(out, lam, lam0, scale, 1e-5)
    want = ref.diff_attention(q[0], k[0], v[0], lam, lam0, scale, 1e-5,
                              window)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


# ------------------------------------------------------------ the caches
def test_one_cache_by_position_eight_rings_nine_states():
    """At the published sizes: ONE layer of "k" and "v" (an "xat" layer
    owns no pool), eight rings, nine states and tails, with the stated
    bytes."""
    cfg = phi4flash.build_model(phi4flash.model_kwargs(PUBLISHED)).cfg
    shapes = cache_shapes(cfg, 16, 12288)
    assert shapes == {
        "k": (1, 16, 12288, 10, 128), "v": (1, 16, 12288, 10, 128),
        "wk": (8, 16, 1536, 10, 128), "wv": (8, 16, 1536, 10, 128),
        "s": (9, 16, 16, 5120), "c": (9, 16, 3, 5120)}
    size = lambda n: int(np.prod(shapes[n])) * jnp.dtype(  # noqa: E731
        cache_dtype(n, jnp.bfloat16)).itemsize
    assert size("k") + size("v") == 16 * 12288 * 5120           # 1.01 GB
    assert size("wk") + size("wv") == 8 * 16 * 1536 * 5120      # 1.01 GB
    assert size("s") == 9 * 16 * 5120 * 16 * 4                  # 47 MB
    assert size("c") == 9 * 16 * 3 * 5120 * 4                   # 9 MB
    assert phi4flash.kv_row_bytes(PUBLISHED, 2.0) == 5120
    read = decode_rows_read(cfg, 12288)([1000, 5000])
    assert read["xkv_rows_live"] == 8 * (1001 + 5001)
    assert read["win_rows_live"] == 2 * 512


# ------------------------------------------------------------ the routes
def test_one_shot_forward_matches_reference(small):
    m, model, params, want = small
    got = model.apply({"params": params}, jnp.asarray(tokens(90))[None])
    np.testing.assert_allclose(got[0], want, atol=1e-4)


@pytest.mark.parametrize("tile", [8, 16])
def test_tiles_then_rows_through_pools_match_reference(small, tile):
    """Prompts that end inside a tile, on its edge and a row past it,
    inside the window (5), past it and past the ring (41, 64, 65), each
    prefilled in tiles, then decoded side by side past the ring's end:
    every logit row is the reference's full forward's."""
    m, model, params, want = small
    seq = tokens(90)
    prompt_lens = (64, 65, 41, 5)
    got = cached_logits(model, params, [seq] * 4, prompt_lens, tile, 96)
    for g, n in zip(got, prompt_lens):
        np.testing.assert_allclose(g, want[n - 1:n - 1 + len(g)], atol=1e-4)


def test_decode_rows_riding_a_tile_equal_decode_alone(small):
    m, model, params, want = small
    seq = tokens(90)
    alone = cached_logits(model, params, [seq] * 2, (64, 41), 16, 96)
    riding = cached_logits(model, params, [seq] * 2, (64, 41), 16, 96,
                           ride=True)
    for a, r, n in zip(alone, riding, (64, 41)):
        np.testing.assert_allclose(r, a, atol=2e-5)
        np.testing.assert_allclose(r, want[n - 1:n - 1 + len(r)], atol=1e-4)


@pytest.mark.parametrize("named", [(10,), (0, 1, 2, 10), (15, 3)])
def test_named_rows_alone_pass_the_cacheless_layers(small, named):
    """A second tile (positions 16..31, eleven real rows) that names rows:
    their logits are what every row through every layer gives, the caches
    it leaves are the same, and the cacheless layers saw those rows
    alone."""
    m, model, params, want = small
    seq = tokens(90)
    pool = kv_cache.SlotPool(model.cfg, 1, 96, 96, 112, jnp.float32)
    names = tuple(pool.shapes)
    every = jax.jit(lambda p, t, c: model.apply(
        {"params": p}, t, cache=c, chunked_prefill=True))
    some = jax.jit(lambda p, t, c, r: model.apply(
        {"params": p}, t, cache=c, chunked_prefill=True, logit_rows=r))
    _, new = every(params, jnp.asarray(seq[None, :16]), dict(
        zip(names, pool.new_scratch()), idx=jnp.int32(0)))
    toks = np.zeros((1, 16), np.int32)
    toks[0, :11] = seq[16:27]
    cache = dict({n: new[n] for n in names}, idx=jnp.int32(16),
                 real=(jnp.arange(16) < 11)[None])
    all_rows, kept = every(params, jnp.asarray(toks), cache)
    got, kept_named = some(params, jnp.asarray(toks), cache,
                           jnp.asarray(named, jnp.int32))
    assert got.shape == (1, len(named), VOCAB)
    np.testing.assert_allclose(got[0], all_rows[0, list(named)], atol=2e-5)
    real = [r for r in named if r < 11]
    np.testing.assert_allclose(got[0, :len(real)] if named == (10,)
                               else all_rows[0, real],
                               want[[16 + r for r in real]], atol=1e-4)
    for n in names:
        np.testing.assert_array_equal(kept[n], kept_named[n])
    # the count the model hands back, taken where it picks the rows: it is
    # every row where none is named
    assert (int(kept["tail_rows"]), int(kept_named["tail_rows"])) \
        == (16, len(named))
    # the shapes the cacheless layers ran on, read off the traced program
    text = str(jax.make_jaxpr(lambda p, t, c, r: model.apply(
        {"params": p}, t, cache=c, chunked_prefill=True, logit_rows=r))(
        params, jnp.asarray(toks), cache, jnp.asarray(named, jnp.int32)))
    assert f"f32[1,{len(named)},{VOCAB}]" in text
    assert f"f32[1,16,{VOCAB}]" not in text


# ---------------------------------------------------------------- engine
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
    reference's argmax at its position; the one cache is counted at eight
    reads a position here (the "att" layer's and... three at this depth),
    and a tile's program runs its cacheless layers on 1 + n_slots rows."""
    m, model, params, _ = small
    prompts = [tokens(70, seed=4), tokens(37, seed=5), tokens(3, seed=6)]
    eng, served = run_engine(model, params, prompts, 12)
    for p, s in zip(prompts, served):
        assert max(ref.teacher_forced_gaps(params, m, list(p), s)) == 0.0
    st = eng.stats()
    assert eng.decode_compile_count == 1 and st["fused_steps"] > 0
    assert st["state_pool_bytes"] == 4 * 2 * 4 * 128 * 4
    assert st["conv_pool_bytes"] == 4 * 2 * 3 * 128 * 4
    assert st["win_pool_bytes"] == 2 * (3 * 2 * 32 * 2 * 16) * 4
    assert st["kv_pool_bytes"] == st["state_pool_bytes"] \
        + st["conv_pool_bytes"] + st["win_pool_bytes"] \
        + 2 * (1 * 2 * 96 * 2 * 16) * 4
    # the "att" layer and the two "xat" layers read ONE cache
    assert st["xkv_rows_live"] == 3 * st["win_rows_live"] or \
        st["xkv_rows_live"] > st["win_rows_live"]
    assert st["xkv_rows_live"] % 3 == 0 and st["xkv_rows_streamed"] % 3 == 0
    assert st["xkv_rows_streamed"] >= st["xkv_rows_live"]
    n = st["prefill_dispatches"]
    assert st["tile_rows"] == n * (16 + 2)
    assert st["tail_rows_run"] == n * (1 + 2)
    # (read off the traced programs, by their rows: a tile with the two
    # slots' rows behind it, and the decode program, whose rows all pass)
    assert eng._tail_rows == {16 + 2: 1 + 2, 1: 2}
    assert st["tile_attn_layers"] == n * 4      # three rings, one cache


def test_the_counters_read_eight_layers_at_the_published_depth():
    m = config(num_hidden_layers=32)
    read = decode_rows_read(build(m).cfg, 96)([0, 5, 23, 40, 70])
    live = 1 + 6 + 24 + 41 + 71
    assert read["xkv_rows_live"] == 8 * live
    assert read["xkv_rows_streamed"] == 8 * 5 * (96 + 1)    # the XLA loop's
    assert read["win_rows_live"] == 1 + 6 + 8 + 8 + 8


def test_a_slot_reused_inherits_nothing(small):
    """The slot's last owner leaves K, V, rings, states and tails behind:
    the next request's tokens are what a fresh engine gives it."""
    m, model, params, _ = small
    a, b = tokens(60, seed=6), tokens(45, seed=7)
    _, fresh = run_engine(model, params, [b], 10, n_slots=1)
    eng, served = run_engine(model, params, [a, b], 10, n_slots=1)
    assert served[1] == fresh[0]
    assert max(ref.teacher_forced_gaps(params, m, list(b), served[1])) == 0.0


def test_the_engine_refuses_a_prefix_cache(small):
    m, model, params, _ = small
    with pytest.raises(ValueError, match="prefix_cache_slots=0"):
        InferenceEngine(model, params, EngineConfig(
            **dict(ENGINE, prefix_cache_slots=2)))
    with pytest.raises(spec.SpecError, match="prefix_cache_slots"):
        phi4flash.model_kwargs(config(engine=dict(ENGINE,
                                                  prefix_cache_slots=2)))


def test_program_rows_meet_the_reference_and_its_states(small):
    """The family's own comparison on the small model: logits through the
    program's tiles-then-rows, the rows at a tile's start, the first and the
    last "s6" layer's state and tail after `insert` and after the last
    token."""
    m, model, params, _ = small
    prompt, gen = tokens(45, seed=8).tolist(), tokens(12, seed=9).tolist()
    score = phi4flash.scored(params, m, prompt, gen,
                             program=phi4flash.program_rows(
                                 params, m, prompt, gen, model=model))
    assert score["logit_rms"] < 1e-4 and 0 < score["edge_rms"] < 1e-4
    assert max(score["state_rel_by_layer"]) < 1e-4
    assert max(score["tail_rel_by_layer"]) < 1e-5


# ---------------------------------------------------------- the controls
SOUND_ONLY = ("sound", "state_in_bf16", "matmuls_below_bf16")


@pytest.mark.parametrize("name", [c for c in phi4flash_controls.CONTROLS
                                  if c not in ("sound",)])
def test_each_planted_fault_moves_the_logits(small, name):
    """Each control of the chip's cell, planted in the small model: the
    program's logits through its own tiles and rows leave the reference's
    (a sound program's distance here is 1e-5), or, for a state kept in
    bf16, the state does."""
    m, model, params, _ = small
    prompt, gen = tokens(45, seed=8).tolist(), tokens(12, seed=9).tolist()
    phi4flash._programs.cache_clear()
    try:
        with phi4flash_controls.planted(name, model, params) as (mm, pp):
            got = phi4flash.program_rows(pp, m, prompt, gen, model=mm)
    finally:
        phi4flash._programs.cache_clear()
    score = phi4flash.scored(params, m, prompt, gen, program=got)
    moved = max(score["logit_rms"], score["first_rms"], score["edge_rms"],
                *score["state_rel_by_layer"], *score["tail_rel_by_layer"])
    assert moved > 1e-3, score
    sound = phi4flash.scored(params, m, prompt, gen,
                             program=phi4flash.program_rows(
                                 params, m, prompt, gen, model=model))
    assert sound["logit_rms"] < 1e-4
