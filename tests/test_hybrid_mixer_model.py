"""The model with layers of two kinds in one stack (block-sparse attention
over blocks selected from pooled keys, and lightning linear attention with a
recurrent state), against its plain reference
(perfbench/families/minicpm_sala_reference.py: the only copy), on the CPU
at a small size in float32: hidden 64, 4 heads / 2 KV heads of 16, six
layers in a non-periodic order with an adjacent sparse pair, blocks of 4
positions, kernels of 2 at stride 1, one initial block, a window of 8, 6
blocks in all, contexts to 96.

The lightning scan meets the recurrence whatever the tiles and chunks, the
block selection is dense attention up to 6 visible blocks and the
reference's set beyond, three routes meet the reference on LOGITS (the
one-shot forward, chunked prefill then decode through caches laid out as the
engine's pools, decode rows riding a tile), the engine's greedy tokens are
the reference's, a slot reused gives what a fresh engine gives, the engine
refuses what does not carry the caches, and each planted fault is caught.
"""
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import spec, weights
from perfbench.families import (minicpm_sala, minicpm_sala_controls,
                                minicpm_sala_reference as ref)
from ray_tpu.inference import kv_cache
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models import TransformerLM
from ray_tpu.models import linear_attention as la
from ray_tpu.models import sparse_attention as sa
from ray_tpu.models.transformer import cache_dtype, cache_shapes

KINDS = ["lightning-attn", "minicpm4", "lightning-attn", "lightning-attn",
         "minicpm4", "minicpm4"]
SPARSE = {"block_size": 4, "kernel_size": 2, "kernel_stride": 1,
          "init_blocks": 1, "window_size": 8, "topk": 6}
GEO = sa.BlockGeometry(4, 2, 1, 1, 8, 6)
VOCAB = 257


def config(**over) -> dict:
    """The family's configuration file at the small size."""
    m = {
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 96,
        "num_hidden_layers": len(KINDS), "mixer_types": list(KINDS),
        "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True,
        "vocab_size": VOCAB, "max_position_embeddings": 512,
        "rope_theta": 10000, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "scale_emb": 12, "scale_depth": 1.4,
        "dim_model_base": 16, "sparse_config": dict(SPARSE),
        "reduced": {"num_hidden_layers": {"published": 32}},
        "param_dtype": "float32",
    }
    m.update(over)
    return m


def build(m: dict):
    kw = minicpm_sala.model_kwargs(m)
    kw.update(dtype="float32", remat=False)
    return minicpm_sala.build_model(kw)


def seeded(model, seed=0):
    """The family's seeded float32 weights; the norms' scales are drawn
    too, so that each matters."""
    params = weights.seeded_params(model, seed, minicpm_sala.weight_rule)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(treedef, [
        a + 0.2 * jax.random.normal(jax.random.PRNGKey(100 + i), a.shape)
        if path[-1].key == "scale" else a
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


@functools.lru_cache(maxsize=None)
def cached_program(model, chunked):
    return jax.jit(lambda params, toks, cache: model.apply(
        {"params": params}, toks, cache=cache, chunked_prefill=chunked))


# ----------------------------------------------------- the lightning layer
def qkv(T, H=4, D=16, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (1, T, H, D), jnp.float32) for k in ks]


@pytest.mark.parametrize("chunk", [1, 5, 16, 128])
@pytest.mark.parametrize("tiles", [(48,), (16, 32), (7, 20, 21), (1,) * 48])
def test_lightning_scan_is_the_recurrence_whatever_the_split(tiles, chunk):
    q, k, v = qkv(48)
    want = np.asarray(ref.lightning(q[0], k[0], v[0]))
    state = jnp.zeros((1, 4, 16, 16), jnp.float32)
    got, at = [], 0
    for n in tiles:
        cut = [a[:, at:at + n] for a in (q, k, v)]
        o, state = (la.lightning_step(*cut, state) if n == 1
                    else la.lightning_scan(*cut, state, chunk=chunk))
        got.append(o)
        at += n
    np.testing.assert_allclose(jnp.concatenate(got, 1)[0], want, atol=2e-5)


@pytest.mark.parametrize("n_real", [0, 1, 13, 32])
def test_rows_no_request_owns_do_not_reach_the_state(n_real):
    """A tile's padded tail neither decays the state nor adds to it."""
    q, k, v = qkv(32)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (1, 4, 16, 16))
    real = (jnp.arange(32) < n_real)[None]
    o, padded = la.lightning_scan(q, k, v, s0, real, chunk=8)
    if n_real:
        o_want, want = la.lightning_scan(
            *(a[:, :n_real] for a in (q, k, v)), s0, chunk=8)
        np.testing.assert_allclose(o[:, :n_real], o_want, atol=2e-5)
    else:
        want = s0
    np.testing.assert_allclose(padded, want, atol=2e-5)


# ---------------------------------------------------- selection by block
def heads(L, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = 3.0 * jax.random.normal(ks[0], (1, L, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, L, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, L, 2, 16), jnp.float32)
    return q, k, v


def test_up_to_topk_visible_blocks_it_is_dense_attention():
    q, k, v = heads(24)                  # 6 blocks: every one is taken
    got = sa.block_attention(q, k, v, GEO)
    want = sa.masked_attention(q, k, v, jnp.tril(jnp.ones((24, 24), bool))[
        None])
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("L", [24, 25, 60, 96])
def test_selection_is_the_references_set(L):
    """One selection a KV group a query; block 0 and the window's blocks
    among them; `topk` blocks wherever more are visible."""
    q, k, v = heads(L)
    pad = -L % 4
    kp = sa.pool_keys(jnp.pad(k, ((0, 0), (0, pad + 2), (0, 0), (0, 0))),
                      L + pad, GEO)
    got = np.asarray(sa.block_select(
        q, kp, jnp.arange(L)[None], GEO))[0]          # [Hkv, L, NB]
    want = np.asarray(ref.selected_blocks(
        q[0], ref.pooled_keys(k[0], SPARSE), jnp.arange(L), L, SPARSE))
    np.testing.assert_array_equal(got, want)
    t = np.arange(L)
    visible = t // 4 + 1
    np.testing.assert_array_equal(got.sum(-1),
                                  np.minimum(visible, 6)[None].repeat(2, 0))
    assert got[:, :, 0].all()                         # the initial block
    for g in range(2):
        for ti in (L - 1, L // 2):
            for b in range(max(ti - 7, 0) // 4, ti // 4 + 1):
                assert got[g, ti, b]                  # the window's blocks
    if L >= 60:
        assert (got[0] != got[1]).any()               # a selection a group


def test_decode_row_attends_what_the_tile_form_attends():
    L = 61
    q, k, v = heads(L)
    want = sa.block_attention(q, k, v, GEO)[:, -1:]
    M = 64
    pad = ((0, 0), (0, M - (L - 1)), (0, 0), (0, 0))
    kc, vc = jnp.pad(k[:, :-1], pad), jnp.pad(v[:, :-1], pad)
    kp = sa.pool_keys(jnp.pad(k, ((0, 0), (0, M + 2 - L), (0, 0), (0, 0))),
                      M, GEO)
    got = sa.block_decode_attention(q[:, -1:], k[:, -1:], v[:, -1:], kc, vc,
                                    kp, jnp.asarray([L - 1]), GEO)
    np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------ three routes, on logits
def test_one_shot_forward_matches_reference(small):
    m, model, params, want = small
    got = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(tokens(90))[None])[0]
    np.testing.assert_allclose(got, want, atol=1e-4)


def cached_logits(model, params, seqs, prompt_lens, tile, max_len,
                  ride=False):
    """Chunked prefill (tiles of `tile` rows into a one-row scratch, as
    the engine runs a prompt, the last tile's tail padded), the scratch
    made a slot of a pool of len(seqs) slots, then decode with every slot
    at its own length, one row a step -> each sequence's logits at every
    position from its prompt's last on. `ride`: the decode rows ride
    behind a further prompt's tiles (the engine's fused step)."""
    cfg = model.cfg
    pool = kv_cache.SlotPool(cfg, len(seqs), max_len, max_len,
                             max_len + tile, jnp.float32)
    names = tuple(pool.shapes)
    tiled, row = cached_program(model, True), cached_program(model, False)
    out = [[] for _ in seqs]

    def prefill(seq, n, slots=None):
        scratch = pool.new_scratch()
        for at in range(0, n, tile):
            real = min(tile, n - at)
            toks = np.zeros((1, tile), np.int32)
            toks[0, :real] = seq[at:at + real]
            cache = dict(zip(names, scratch), idx=jnp.int32(at),
                         real=(jnp.arange(tile) < real)[None])
            if slots is not None and at == 0:
                lens, last = slots
                toks = np.concatenate([toks, last[None]], 1)
                cache["real"] = jnp.concatenate(
                    [cache["real"], jnp.ones((1, len(lens)), bool)], 1)
                cache["slots"] = dict(zip(names, pool.pools()),
                                      idx=jnp.asarray(lens), on=True)
            lg, new = tiled(params, jnp.asarray(toks), cache)
            scratch = tuple(new[n_] for n_ in names)
            if "slots" in cache:
                pool.rebind(tuple(new["slots"][n_] for n_ in names))
                rows = lg[0, tile:]
        return scratch, lg[0, real - 1], (rows if slots is not None
                                          else None)

    for b, (seq, n) in enumerate(zip(seqs, prompt_lens)):
        scratch, lg, _ = prefill(seq, n)
        pool.insert(scratch, b)
        out[b].append(lg)
    lens = np.asarray(prompt_lens, np.int32)
    steps = min(len(s) - n for s, n in zip(seqs, prompt_lens))
    for step in range(steps):
        last = np.asarray([s[n + step] for s, n in zip(seqs, prompt_lens)],
                          np.int32)
        if ride and step == 1:
            # this step's rows ride behind another prompt's first tile
            _, _, lg = prefill(tokens(tile + 5, seed=77), tile + 5,
                               (lens, last))
        else:
            lg, new = row(params, jnp.asarray(last)[:, None],
                          dict(zip(names, pool.pools()),
                               idx=jnp.asarray(lens)))
            pool.rebind(tuple(new[n_] for n_ in names))
            lg = lg[:, 0]
        for b in range(len(seqs)):
            out[b].append(lg[b])
        lens = lens + 1
    return [np.stack(o) for o in out]


@pytest.mark.parametrize("tile", [8, 16, 32])
def test_prefill_then_decode_through_pools_matches_reference(small, tile):
    """Prompts of several lengths (one ends on a tile's edge, one a row
    past it), each prefilled in several tiles, then decoded side by side:
    every logit row is the reference's full forward's."""
    m, model, params, want = small
    seq = tokens(90)
    prompt_lens = (64, 65, 41)
    got = cached_logits(model, params, [seq] * 3, prompt_lens, tile, 96)
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
    """Two prompts in flight (the second's tiles carry the first's decode
    rows): every served token is the reference's argmax at its position."""
    m, model, params, _ = small
    prompts = [tokens(70, seed=4), tokens(37, seed=5)]
    eng, served = run_engine(model, params, prompts, 12)
    for p, s in zip(prompts, served):
        gaps = ref.teacher_forced_gaps(params, m, list(p), s)
        assert max(gaps) == 0.0
    st = eng.stats()
    assert eng.decode_compile_count == 1 and st["fused_steps"] > 0
    assert st["state_pool_bytes"] == 3 * 2 * 4 * 16 * 16 * 4
    assert st["kv_pool_bytes"] == st["state_pool_bytes"] \
        + 4 * (2 * 3 * 2 * 96 * 2 * 16) + 4 * (3 * 2 * 96 * 2 * 16)
    # a decode row at 71.. positions: 18+ blocks visible, 6 attended
    assert 0 < st["blk_rows_read"] < st["blk_rows_live"]
    assert st["blk_rows_read"] <= 24 * 2 * 12


def test_a_slot_reused_gives_what_a_fresh_engine_gives(small):
    """The slot's last owner leaves K, V, pooled keys and STATES behind:
    the next request's are its own."""
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
    assert max(ref.teacher_forced_gaps(params, m, list(b), served[1])) == 0.0


def test_the_tile_program_unembeds_the_rows_it_samples(small):
    """The model of two kinds of layer, like every other model's tile
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
    with pytest.raises(spec.SpecError, match="prefix_cache_slots"):
        minicpm_sala.model_kwargs(config(engine={
            "max_len": 96, "prefix_cache_slots": 1}))


# ------------------------------------------------------------ the controls
@pytest.mark.parametrize("control", list(minicpm_sala_controls.CONTROLS))
def test_planted_fault_is_caught(small, control):
    """Each control of the cell, planted at the small size in the path
    through tiles, pools and decode rows, moves the logits off the
    reference's; the sound program does not."""
    m, model, params, want = small
    seq = tokens(90)
    cached_program.cache_clear()        # a planted function is traced anew
    try:
        with minicpm_sala_controls.planted(control, model, params) as (
                mm, pp):
            got = cached_logits(mm, pp, [seq] * 2, (64, 41), 16, 96)
    finally:
        cached_program.cache_clear()
    off = max(float(np.abs(g - want[n - 1:n - 1 + len(g)]).max())
              for g, n in zip(got, (64, 41)))
    assert (off < 1e-4) == (control == "sound"), off


def run_cfg(m, **tolerance):
    """The small configuration as a run of the cell holds it."""
    return dict(m, engine=dict(ENGINE, max_ongoing_requests=8),
                family="minicpm_sala", _family_file=minicpm_sala.__file__,
                reference_tolerance=dict(
                    {"logit_gap": 1e-3, "share_within": 1.0,
                     "logit_rms": 1e-4, "state_rel": 1e-4}, **tolerance))


@pytest.mark.parametrize("control", ["sound", "state_of_last_owner_left",
                                     "state_in_bf16"])
def test_controls_are_judged_as_a_run_is(small, control):
    """The tool that reads the controls on the chip, at the small size:
    slots that have had an owner, the cases in flight together, the
    program's logits taken with the fault planted, the family's two
    numbers folded as a run folds them (here in float32 the sound program
    leaves every token at a gap of 0 and its logits 1e-5 from the
    reference's). A state kept in bf16 flips no token here and fails by
    the second number alone."""
    m, model, params, _ = small
    cfg = run_cfg(m)
    cases = [(tokens(70, seed=11).tolist(), 24),
             (tokens(33, seed=12).tolist(), 24)]
    minicpm_sala._programs.cache_clear()    # a planted function is traced
    try:
        with minicpm_sala_controls.planted(control, model, params) as (
                mm, pp):
            served = minicpm_sala_controls.serve(mm, pp, cfg, cases, 0,
                                                 warm=(40, 4))
            rows = [minicpm_sala.program_rows(pp, cfg, p, g, model=mm)
                    for (p, _), g in zip(cases, served)]
    finally:
        minicpm_sala._programs.cache_clear()
    row = minicpm_sala_controls.judge(cfg, params, cases, served, rows)
    assert row["passes"] == (control == "sound"), row["beyond"]
    for key in ("logit_rms", "state_rel"):
        over = max(row[key + "_by_case"]) > row[key + "_limit"]
        assert over == (control != "sound"), row[key + "_by_case"]
    if control == "state_in_bf16":
        assert sum(row["tokens_beyond_by_case"]) == 0
        assert row["beyond"] == row["n_tokens"]


def test_the_harness_call_reads_both_numbers(small):
    """replica.bench_reference's call: the family builds the program from
    the configuration (bf16 activations, as served), takes its logits on
    the served tokens and folds their distance from the reference's into
    the gaps: within a limit above bf16's own rounding nothing changes,
    under a limit below it every token counts as beyond."""
    m, model, params, _ = small
    prompt = tokens(40, seed=13).tolist()
    _, (served,) = run_engine(model, params, [prompt], 12)
    plain = ref.teacher_forced_gaps(params, m, prompt, served, pad_to=128)
    minicpm_sala._programs.cache_clear()
    try:
        gaps, spread = minicpm_sala.teacher_forced_gaps(
            params, run_cfg(m, logit_rms=1.0, state_rel=1.0), prompt,
            served, pad_to=128, with_spread=True)
        assert gaps == plain and spread > 0
        score = minicpm_sala.scored(params, run_cfg(m), prompt, served, 128)
        dev = score["logit_rms"]
        assert len(score["logit_rms_each"]) == 12
        assert np.shape(score["state_rel"]) == (3, 4)
        assert 1e-4 < dev < 1.0                         # bf16's rounding
        assert 1e-4 < minicpm_sala.state_number(score["state_rel"]) < 0.01
        tight = minicpm_sala.teacher_forced_gaps(
            params, run_cfg(m, logit_rms=dev / 2, state_rel=1.0), prompt,
            served, pad_to=128)
        assert min(tight) == pytest.approx(2e-3)
    finally:
        minicpm_sala._programs.cache_clear()


@pytest.mark.parametrize("control", ["sound", "state_in_bf16"])
def test_state_number_sees_the_states_type_under_bf16_activations(small,
                                                                  control):
    """As on the chip, where the activations are bf16 and a count of
    tokens or the logits' distance cannot tell a state kept in bf16: the
    first lightning layer's slow heads can (0.004-0.005 against
    0.014-0.017 over 240 decode rows here)."""
    m, _, params, _ = small
    kw = minicpm_sala.model_kwargs(m)
    kw.update(remat=False)                       # bf16 activations
    model = minicpm_sala.build_model(kw)
    cfg = run_cfg(m)
    cfg["engine"] = dict(cfg["engine"], max_len=320)
    prompt, served = tokens(60, seed=2).tolist(), tokens(240, seed=52).tolist()
    minicpm_sala._programs.cache_clear()
    try:
        with minicpm_sala_controls.planted(control, model, params) as (
                mm, pp):
            program = minicpm_sala.program_rows(pp, cfg, prompt, served,
                                                model=mm)
    finally:
        minicpm_sala._programs.cache_clear()
    score = minicpm_sala.scored(params, cfg, prompt, served, 384, program)
    got = minicpm_sala.state_number(score["state_rel"])
    assert (got < 0.008) == (control == "sound"), got


# ------------------------------------------------------ family and config
def test_family_seeds_every_leaf_and_counts_the_new_mathematics(small):
    m, model, params, _ = small
    shapes = cache_shapes(model.cfg, 2, 96)
    assert shapes == {"k": (3, 2, 96, 2, 16), "v": (3, 2, 96, 2, 16),
                      "kp": (3, 2, 96, 2, 16), "s": (3, 2, 4, 16, 16)}
    assert cache_dtype("s", jnp.bfloat16) == jnp.float32
    n = sum(a.size for a in jax.tree.leaves(params)
            if a.ndim > 1) - VOCAB * 64
    assert minicpm_sala.stored_param_bytes(m, 1.0) == n + VOCAB * 64
    # a step moves the weights, each state in and out, the selected rows
    base = minicpm_sala.decode_step_bytes(m, [0.0, 0.0], 4.0, 4.0)
    assert base == 4.0 * n + 3 * 2 * (2 * 4 * 16 * 16 * 4.0)
    more = minicpm_sala.decode_step_bytes(m, [90.0, 0.0], 4.0, 4.0)
    assert more - base == 3 * (2 * 24 + 90) * 2 * 16 * 4.0
    assert minicpm_sala.lightning_flops(m, 10) == 4 * 16 * 16 * 4 * 10


def test_shipped_configuration_states_the_published_widths():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "minicpm-sala")
    kw = minicpm_sala.model_kwargs(cfg)
    assert (kw["d_model"], kw["d_ff"], kw["n_heads"], kw["n_kv_heads"],
            kw["head_dim"], kw["vocab_size"]) == (4096, 16384, 32, 2, 128,
                                                  73448)
    kinds = "".join("S" if k == "blk" else "L" for k in kw["mixer_kinds"])
    assert kinds == "LLLSLLLLLLSS"
    published = cfg["reduced"]["mixer_types"]["published"]
    assert published[6:18] == cfg["mixer_types"] and len(published) == 32
    assert abs(kw["residual_scale"] - 1.4 / 32 ** 0.5) < 1e-12
    assert kw["logit_scale"] == 1 / 16
    with open(os.path.join(spec.ROOT, "perfbench", "traffic",
                           "longdoc-pool.json")) as f:
        mix = json.load(f)
    assert mix["clients"] == cfg["engine"]["n_slots"] == 16
    model = minicpm_sala.build_model(kw)
    shapes = cache_shapes(model.cfg, 16, cfg["engine"]["max_len"])
    nbytes = {n: int(np.prod(s)) * (4 if n == "s" else 2)
              for n, s in shapes.items()}
    assert nbytes["s"] == 9 * 16 * 32 * 128 * 128 * 4
    assert nbytes["kp"] * 16 == nbytes["k"]
    assert isinstance(model, TransformerLM) and dataclasses.is_dataclass(
        model.cfg)
