"""The model with latent attention (one cached row of latent + rope key a
position a layer for all heads, YaRN's rotary on a part of a head, an
expanding tile form and an absorbed decode-row form), a leading dense layer
and a sigmoid-routed expert layer with a selection bias and a shared expert,
against its plain reference (perfbench/families/sarvam_mla_reference.py: the
only copy), on the CPU at a small size in float32: hidden 64, 4 heads of
16 + 8 against a latent of 32 and values of 16, YaRN's original length 16
(factor 40), 1 dense + 2 expert layers of 16 experts of 24 with 8 a token
(`routed_scaling_factor` 2.5), vocabulary 128, contexts to 90 in key blocks
of 8.

Three routes meet the reference on LOGITS at lengths that end inside, at
and past a key block and past YaRN's original length (the one-shot forward;
prefill by tiles then decode through the latent cache; decode rows riding a
tile); the absorbed row form is the expanded form on one latent to
rounding; the pool is ONE array of latent + rope values a position with no
head axis (the positions last), 1,152 B a position a layer at the published
widths in bf16; the
engine's greedy tokens are the reference's (through the XLA loops and
through each of the two Pallas kernels, interpreted), a slot reused inherits
nothing, the engine counts the latents its rows pass over and refuses a
prefix cache; YaRN at factor 1 is `rope` bit for bit and at 40 the reference's
frequencies and scale; the ranks' shares of an expert layer add up to the
whole layer; and each planted fault moves the logits.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import spec, weights
from perfbench.families import (sarvam_mla, sarvam_mla_controls,
                                sarvam_mla_reference as ref)
from ray_tpu.inference import kv_cache
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models import latent_attention as la
from ray_tpu.models import transformer as tr
from ray_tpu.models.moe import MoEMLP
from ray_tpu.models.transformer import cache_shapes

VOCAB, TILE, MAX_LEN = 128, 8, 104
with open(os.path.join(spec.ROOT, "perfbench", "configs",
                       "sarvam-105b.json")) as f:
    PUBLISHED = json.load(f)


def config(**over) -> dict:
    """The family's configuration file at the small size: the published
    file with its widths cut, every switch as published, every expert
    held."""
    m = {k: v for k, v in PUBLISHED.items() if k != "reference_tolerance"}
    m.update(hidden_size=64, num_attention_heads=4, q_head_dim=24,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             kv_lora_rank=32, head_dim=40, intermediate_size=96,
             moe_intermediate_size=24, num_hidden_layers=3, num_experts=16,
             num_local_experts=16, vocab_size=VOCAB,
             max_position_embeddings=512, param_dtype="float32",
             rope_scaling=dict(PUBLISHED["rope_scaling"],
                               original_max_position_embeddings=16),
             deployment={"expert_rank": 0},
             program={"capacity_factor": 16.0},
             engine=dict(PUBLISHED["engine"], n_slots=3, max_len=MAX_LEN,
                         prefill_chunk=4, prefill_budget=TILE))
    m.update(over)
    return m


def build(m: dict, **over):
    kw = sarvam_mla.model_kwargs(m)
    kw.update(dtype="float32", remat=False, logits_fp32=True, **over)
    return sarvam_mla.build_model(kw)


def seeded(model, seed=0):
    """The family's seeded float32 weights; every norm's scale is drawn
    too, so that each matters, and the bias is made large enough to
    choose."""
    params = weights.seeded_params(model, seed, sarvam_mla.weight_rule)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(treedef, [
        a + 0.2 * jax.random.normal(jax.random.PRNGKey(100 + i), a.shape)
        if path[-1].key in ("scale", "router_bias") else a
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


def cached_program(model, chunked):
    """(Traced anew each time: a planted fault must not outlive its test
    in a cache.)"""
    return jax.jit(lambda params, toks, cache: model.apply(
        {"params": params}, toks, cache=cache, chunked_prefill=chunked))


def through_the_cache(model, params, toks, tile=TILE):
    """Logits [L, vocab] of `toks`: prefill by tiles of `tile` (the last
    padded) into a cache laid out as the engine's pool, then, from the
    last whole tile on, one decode row a token."""
    L = len(toks)
    cache = tr.init_cache(model.cfg, 1, MAX_LEN + tile, jnp.float32)
    tiled, row = cached_program(model, True), cached_program(model, False)
    out, at = [], 0
    n_tiled = max(tile, (L * 2 // 3) // tile * tile)
    while at < min(n_tiled, L):
        n = min(tile, L - at, n_tiled - at)
        t = np.zeros((1, tile), np.int32)
        t[0, :n] = toks[at:at + n]
        lg, cache = tiled(params, jnp.asarray(t), dict(
            cache, idx=jnp.int32(at), real=(jnp.arange(tile) < n)[None]))
        out.append(lg[0, :n])
        at += n
    for i in range(at, L):
        lg, cache = row(params, jnp.asarray(toks[i:i + 1])[None], dict(
            cache, idx=jnp.asarray([i], jnp.int32)))
        out.append(lg[0])
    return np.asarray(jnp.concatenate(out))


# ------------------------------------------------------------- the pool
def test_one_pool_of_latent_rows_and_no_head_axis(small):
    _, model, _, _ = small
    assert cache_shapes(model.cfg, 3, MAX_LEN) == {"lat": (3, 3, 40, MAX_LEN)}
    assert tr.KIND_CACHES["mla"] == ("lat",) \
        and tr.CACHE_POS_AXIS["lat"] == -1 \
        and tr.POOL_BYTES_KEYS["latent_pool_bytes"] == ("lat",)
    # at the published widths: 576 values a position a layer, 1,152 B in
    # bf16, whatever the 64 heads
    full = sarvam_mla.build_model(sarvam_mla.model_kwargs(PUBLISHED)).cfg
    assert cache_shapes(full, 16, 18432) == {"lat": (8, 16, 576, 18432)}
    pool = kv_cache.SlotPool(full, 1, 64, 64, 72, jnp.bfloat16)
    assert pool.nbytes() == pool.nbytes(("lat",)) == 8 * 64 * 1152
    assert [s.shape for s in pool.new_scratch()] == [(8, 1, 576, 72)]
    assert tr.decode_rows_read(full, 18432)([]) == {
        "mla_rows_streamed": 0, "mla_rows_live": 0}
    assert tr.tile_attention_layers(full, 1024, 18432 + 1024) == (8, 0)


def test_the_kind_stands_alone_in_its_stack(small):
    _, model, _, _ = small
    with pytest.raises(ValueError, match="every layer of the stack"):
        dataclasses.replace(model.cfg, mixer_kinds=("mla", "att", "mla"))
    with pytest.raises(ValueError, match="rope_yarn"):
        dataclasses.replace(model.cfg, mixer_kinds=("att",) * 3)


# ------------------------------------------------- against the reference
def test_one_shot_forward_meets_the_reference(small):
    _, model, params, want = small
    got = model.apply({"params": params}, jnp.asarray(tokens(90))[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("L", [
    5,       # inside the first key block
    8,       # at its end
    9,       # past it
    16, 17,  # at and past YaRN's original length
    30,      # tiles that meet several blocks
    90])     # and more, rows far past the original length
def test_tiles_then_rows_through_the_latents_meet_the_reference(small, L):
    _, model, params, want = small
    got = through_the_cache(model, params, tokens(90)[:L])
    np.testing.assert_allclose(got, want[:L], atol=2e-4)


def test_the_row_form_is_the_expanded_form_on_one_latent():
    """One layer's attention alone: a row at position n against a pool of
    n latents, absorbed, and the same row as the last of a sequence,
    expanded over per-head K and V."""
    H, Dn, Dr, Dv, R, n = 4, 16, 8, 16, 32, 21
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, n + 1, H, Dn + Dr))
    lat = jax.random.normal(ks[1], (1, n + 1, R + Dr))
    w_uk = jax.random.normal(ks[2], (R, H, Dn)) / R ** 0.5
    w_uv = jax.random.normal(ks[3], (R, H, Dv)) / R ** 0.5
    want = la.expanded_attention(q, lat, w_uk, w_uv, 0.37)[:, -1:]
    # layer 1 of a two-layer pool of 32 positions (blocks of 32): the
    # places past n hold another layer's numbers, which no row may meet
    kept = jnp.swapaxes(lat, 1, 2)          # as the pool keeps them
    pool = jnp.stack([jnp.full((1, R + Dr, 32), 9.0),
                      jnp.pad(kept[..., :n], ((0, 0), (0, 0), (0, 32 - n)),
                              constant_values=5.0)])
    got = la.row_attention(q[:, -1:], lat[:, -1:], pool, jnp.int32(1),
                           jnp.asarray([n]), w_uk, w_uv, 0.37)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and the tile form over the same sequence, written into its scratch
    tile = la.tile_attention(q[:, 16:], jnp.pad(
        kept[..., :16], ((0, 0), (0, 0), (0, 16))), kept[..., 16:],
        jnp.int32(16), w_uk, w_uv, 0.37)
    np.testing.assert_allclose(
        tile, la.expanded_attention(q, lat, w_uk, w_uv, 0.37)[:, 16:],
        atol=2e-5)


def _slots_cache(model, params, lens, toks):
    """A 3-slot pool whose slots hold the first lens[b] of `toks[b]`."""
    cache = tr.init_cache(model.cfg, len(lens), MAX_LEN, jnp.float32)
    tiled = cached_program(model, True)
    for b, n in enumerate(lens):
        one = tr.init_cache(model.cfg, 1, MAX_LEN, jnp.float32)
        for at in range(0, n, TILE):
            k = min(TILE, n - at)
            t = np.zeros((1, TILE), np.int32)
            t[0, :k] = toks[b][at:at + k]
            _, one = tiled(params, jnp.asarray(t), dict(
                one, idx=jnp.int32(at), real=(jnp.arange(TILE) < k)[None]))
        cache["lat"] = cache["lat"].at[:, b].set(one["lat"][:, 0])
    return cache


ROW_FORMS = pytest.mark.parametrize("form", ["loop", "row_kernel"])


@ROW_FORMS
def test_rows_behind_a_tile_give_what_they_give_alone(small, request, form):
    """The engine's step: a tile of another prompt and, behind it, one
    decode row a slot at its own length (read by the XLA loop, or through
    the Pallas kernel where the predicate takes the pool)."""
    _, model, params, _ = small
    if form == "row_kernel":
        request.getfixturevalue(form)
    lens = [40, 24, 9]
    toks = [tokens(60, seed=10 + b) for b in range(3)]
    slots = _slots_cache(model, params, lens, toks)
    nxt = jnp.asarray([toks[b][n] for b, n in enumerate(lens)], jnp.int32)
    alone, _ = cached_program(model, False)(
        params, nxt[:, None], dict(slots, idx=jnp.asarray(lens, jnp.int32)))
    prompt = tokens(30, seed=20)
    scratch = tr.init_cache(model.cfg, 1, MAX_LEN + TILE, jnp.float32)
    tiled = cached_program(model, True)
    for at in (0, 8, 16):
        tile_alone, after = tiled(params, jnp.asarray(
            prompt[at:at + TILE])[None], dict(scratch, idx=jnp.int32(at)))
        if at < 16:
            scratch = after
    both, new = tiled(
        params, jnp.concatenate([jnp.asarray(prompt[16:24]), nxt])[None],
        dict(scratch, idx=jnp.int32(16),
             real=jnp.ones((1, TILE + 3), bool),
             slots=dict(lat=slots["lat"], idx=jnp.asarray(lens, jnp.int32),
                        on=jnp.asarray(True))))
    np.testing.assert_allclose(both[0, TILE:], alone[:, 0], atol=2e-4)
    np.testing.assert_allclose(both[0, :TILE], tile_alone[0], atol=2e-4)
    # and each row's latent was written at its slot's length, alone
    for b, n in enumerate(lens):
        assert float(jnp.abs(new["slots"]["lat"][:, b, :, n]).sum()) > 0
        np.testing.assert_array_equal(
            np.delete(np.asarray(new["slots"]["lat"][:, b]), n, axis=2),
            np.delete(np.asarray(slots["lat"][:, b]), n, axis=2))


# ----------------------------------------------------------- the engine
def _engine(model, params, **kw):
    cfg = dict(n_slots=3, max_len=MAX_LEN, prefill_chunk=4,
               prefill_budget=TILE)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg))


def _greedy(eng, prompts, n_new):
    hs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, n_new)]
    for _ in range(2000):
        if not eng.step():
            break
    return [list(h) for h in hs]


def _is_the_references_greedy(params, m, prompt, generated):
    """Each served token is its position's largest reference logit (one
    pass of the reference over prompt and served tokens; float32 on both
    sides, so a gap is a near-tie's)."""
    gaps = ref.teacher_forced_gaps(params, m, list(prompt), generated)
    return len(gaps) == len(generated) and max(gaps) < 1e-3


def test_engine_greedy_tokens_are_the_references(small):
    """Three requests in flight together, each past YaRN's original
    length, the others' rows riding each one's tiles."""
    m, model, params, _ = small
    prompts = [tokens(n, seed=30 + n) for n in (57, 21, 35)]
    n_new = [12, 30, 20]
    got = _greedy(_engine(model, params), prompts, n_new)
    for p, n, g in zip(prompts, n_new, got):
        assert len(g) == n and _is_the_references_greedy(params, m, p, g)


@ROW_FORMS
def test_a_slot_reused_inherits_nothing_from_its_last_owner(small, request,
                                                            form):
    """One slot: a long request fills it, then a shorter one takes it and
    gives what a fresh engine gives, which is the reference's."""
    m, model, params, _ = small
    if form == "row_kernel":
        request.getfixturevalue(form)
    long_, short = tokens(70, seed=41), tokens(9, seed=42)
    eng = _engine(model, params, n_slots=1)
    _greedy(eng, [long_], [10])
    again = _greedy(eng, [short], [12])[0]
    fresh = _greedy(_engine(model, params, n_slots=1), [short], [12])[0]
    assert again == fresh and len(again) == 12
    assert _is_the_references_greedy(params, m, short, again)


@pytest.fixture
def tile_kernel(monkeypatch):
    """The tile's predicate made to answer as on the chip for these small
    widths (no lane tiles: the interpreter asks for none) and the latent
    kernel of ops/tile_attention.py made to interpret; -> the shapes of q
    it was handed."""
    from ray_tpu.models import sparse_attention as sa
    from ray_tpu.ops import tile_attention as ta
    calls, compiled = [], ta.latent_tile_attention

    def interpreted(*a):
        calls.append(a[0].shape)
        return compiled(*a, interpret=True)

    monkeypatch.setattr(ta, "latent_fits", lambda *a: True)
    monkeypatch.setattr(sa, "_latent_tile_kernel_takes", ta.latent_fits)
    monkeypatch.setattr(ta, "latent_tile_attention", interpreted)
    return calls


def test_engine_greedy_tokens_through_the_tile_kernel_are_the_references(
        small, tile_kernel):
    """`test_engine_greedy_tokens_are_the_references` with every layer's
    tile through the Pallas kernel: traced once a layer of the one tile
    program, a tile of 8 rows of 4 heads."""
    m, model, params, _ = small
    prompts = [tokens(n, seed=60 + n) for n in (57, 21, 35)]
    n_new = [12, 30, 20]
    got = _greedy(_engine(model, params), prompts, n_new)
    assert tile_kernel == [(1, TILE, 4, 24)] * 3
    for p, n, g in zip(prompts, n_new, got):
        assert len(g) == n and _is_the_references_greedy(params, m, p, g)


@pytest.fixture
def row_kernel(monkeypatch):
    """`tile_kernel`'s twin for the decode rows: `row_attention`'s
    predicate made to answer as on the chip for these small widths and the
    latent kernel of ops/decode_attention.py made to interpret; -> the
    shapes of the absorbed rows it was handed."""
    from ray_tpu.models import sparse_attention as sa
    from ray_tpu.ops import decode_attention as da
    calls, compiled = [], da.latent_pool_decode_attention

    def interpreted(qa, *a):
        calls.append(qa.shape)
        return compiled(qa, *a, interpret=True)

    monkeypatch.setattr(da, "latent_fits", lambda *a: True)
    monkeypatch.setattr(sa, "_latent_row_kernel_takes", da.latent_fits)
    monkeypatch.setattr(da, "latent_pool_decode_attention", interpreted)
    return calls


def test_engine_greedy_tokens_through_the_row_kernel_are_the_references(
        small, row_kernel, monkeypatch):
    """`test_engine_greedy_tokens_are_the_references` with every layer's
    decode rows through the Pallas kernel, alone and behind a tile: traced
    once a layer of the decode program and of the tile program, three
    slots' rows of 4 heads absorbed to 32 + 8 values. And what the engine
    counts of their reads: where the kernel reads, each row's own key
    blocks of 8 and its own position; where the loop runs, the longest
    row's for every row."""
    m, model, params, _ = small
    prompts = [tokens(n, seed=70 + n) for n in (57, 21, 35)]
    n_new = [12, 30, 20]
    eng = _engine(model, params)
    issued, count = [], eng._rows_read_of
    eng._rows_read_of = lambda lens: issued.append(lens) or count(lens)
    got = _greedy(eng, prompts, n_new)
    assert row_kernel == [(3, 4, 40)] * 6
    for p, n, g in zip(prompts, n_new, got):
        assert len(g) == n and _is_the_references_greedy(params, m, p, g)
    blocks = lambda n: -(-n // 8) * 8 + 1                    # noqa: E731
    by_row = sum(blocks(n) for lens in issued for n in lens)
    by_longest = sum(len(lens) * blocks(max(lens)) for lens in issued)
    assert eng.stats()["mla_rows_streamed"] == by_row < by_longest
    from ray_tpu.models import sparse_attention as sa
    monkeypatch.setattr(sa, "_latent_row_kernel_takes", lambda *a: False)
    assert sum(count(lens)["mla_rows_streamed"] for lens in issued) \
        == by_longest


@pytest.mark.parametrize("kernel", [False, True], ids=["loop", "kernel"])
def test_engine_counts_the_latents_and_refuses_a_prefix_cache(
        small, request, kernel):
    _, model, params, _ = small
    if kernel:
        request.getfixturevalue("tile_kernel")
    eng = _engine(model, params)
    _greedy(eng, [tokens(40, seed=50)], [6])
    st = eng.stats()
    assert st["latent_pool_bytes"] == st["kv_pool_bytes"] \
        == 3 * 3 * MAX_LEN * 40 * 4
    # five decode rows at lengths 40..44 (the first token is the last
    # tile's): each is live over its length and its own, and passes over
    # whole key blocks of 8 up to its last (one slot live: the longest)
    assert st["mla_rows_live"] == sum(n + 1 for n in range(40, 45))
    assert st["mla_rows_streamed"] == 1 * (40 + 1) + 4 * (48 + 1)
    # five tiles of 8 rows hold the prompt's 40: each dispatch sends the
    # three layers' tiles through the blocked loop, or, where the
    # predicate takes them, every one through the kernel
    assert st["prefill_dispatches"] == 5
    assert st["tile_attn_layers"] == 5 * 3
    assert st["tile_kernel_layers"] == (5 * 3 if kernel else 0)
    with pytest.raises(ValueError, match="beyond K and V"):
        _engine(model, params, prefix_cache_slots=2)


# ------------------------------------------------------------- the rotary
def test_yarn_at_factor_one_is_rope_bit_for_bit():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 50, 3, 64))
    pos = jnp.broadcast_to(jnp.arange(4000, 4050)[None], (2, 50))
    one = tr.Yarn(1.0, 4096, 32.0, 1.0, 1.0)
    assert (tr.yarn_blend(64, 10000.0, one) == 1.0).all()
    np.testing.assert_array_equal(
        jax.jit(lambda x, p: tr.rope(x, p, 10000.0, one))(x, pos),
        jax.jit(lambda x, p: tr.rope(x, p, 10000.0))(x, pos))


def test_yarn_at_forty_is_the_references_frequencies_and_scale():
    rs = PUBLISHED["rope_scaling"]
    yarn = sarvam_mla.build_model(
        sarvam_mla.model_kwargs(PUBLISHED)).cfg.rope_yarn
    assert yarn == tr.Yarn(40, 4096, 32, 1, 1)
    plain = 10000.0 ** (-np.arange(0, 64, 2, dtype=np.float32) / 64)
    blend = tr.yarn_blend(64, 10000.0, yarn)
    np.testing.assert_allclose(plain * blend,
                               ref.yarn_inv_freq(64, 10000.0, rs), rtol=1e-6)
    # the fast dimensions keep their frequency, the slow ones a fortieth,
    # a ramp between the correction dimensions 10 and 23
    assert (blend[:11] == 1.0).all() and np.allclose(blend[23:], 1 / 40)
    assert (np.diff(blend[10:24]) < 0).all()
    full = sarvam_mla.build_model(sarvam_mla.model_kwargs(PUBLISHED)).cfg
    m2 = (0.1 * np.log(40.0) + 1.0) ** 2
    assert la.softmax_scale(full) == pytest.approx(192 ** -0.5 * m2) \
        == pytest.approx(ref.softmax_scale(PUBLISHED))
    assert la.softmax_scale(dataclasses.replace(
        full, rope_yarn=None)) == 192 ** -0.5
    # rotated: the reference's rotary at the same positions
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 60, 2, 64))
    got = tr.rope(x, jnp.arange(60)[None], 10000.0, yarn)
    want = ref._rope(x[0], ref.yarn_inv_freq(64, 10000.0, rs), 1.0)
    np.testing.assert_allclose(got[0], want, atol=1e-5)


# ------------------------------------------------------ the expert layer
def test_ranks_shares_add_up_to_the_whole_layer_shared_counted_once():
    """Eight ranks of two experts each, eight picks a token, the gates
    times 2.5: every rank computes the shared expert, so the shares' sum
    holds it eight times; less seven of it, it is the uncut layer, which
    is the reference's."""
    m = config()
    cfg = build(m).cfg
    assert (cfg.expert_top_k, cfg.route_scale, cfg.router) == (
        8, 2.5, "sigmoid")
    whole = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64))
    from flax.core import meta
    params = meta.unbox(whole.init(jax.random.PRNGKey(4), x)["params"])
    params["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(5),
                                                    (16,))
    want, _ = whole.apply({"params": params}, x, exact=True)
    np.testing.assert_allclose(
        want[0], ref.expert_layer(x[0], params, m), atol=2e-5)
    shared_only = ref._fns(m)[5](x[0], *(params[f"shared_{w}"]["kernel"]
                                         for w in ("gate", "up", "down")))
    total = 0.0
    for rank in range(8):
        part = MoEMLP(dataclasses.replace(cfg, experts_held=(2 * rank, 2)))
        mine = dict(params, **{w: params[w][2 * rank:2 * rank + 2]
                               for w in ("gate", "up", "down")})
        got, _ = part.apply({"params": mine}, x, exact=True)
        np.testing.assert_allclose(got[0], ref.expert_layer(x[0], mine, dict(
            m, num_local_experts=2, deployment={"expert_rank": rank})),
            atol=2e-5)
        total = total + got[0]
    np.testing.assert_allclose(total - 7 * shared_only, want[0], atol=1e-4)


# ------------------------------------------------------ planted faults
@pytest.mark.parametrize("name", [
    n for n in sarvam_mla_controls.CONTROLS if n != "sound"])
def test_each_planted_fault_moves_the_logits(small, name):
    """The controls of the cell's `correct`, at the small size: each one
    moves the logits by more than float32's rounding does, through tiles
    and rows past YaRN's original length."""
    m, model, params, want = small
    toks = tokens(90)[:60]
    with sarvam_mla_controls.planted(name, model, params) as (faulty, p):
        got = through_the_cache(faulty, p, toks)
        route = sarvam_mla.route_deviation(p, m, faulty)
    off = np.abs(got - want[:60]).max(-1)
    assert off.max() > 5e-3
    if name == "row_rope_key_unrotated":
        # the tiles' rows are sound; the first decode row attends only
        # its own key unrotated, the later ones the cached ones too
        assert off[:40].max() < 2e-4 < off[41:].max()
    # the expert layer's own number sees the faults of the expert layer
    # and no other (it is float32 arithmetic on whatever weights it is
    # given, rounded ones too)
    assert (route > 1e-3) == (name in ("bias_weighs",
                                       "shared_expert_dropped")), route
    assert sarvam_mla.route_deviation(params, m, model) < 1e-5
    sound = through_the_cache(model, params, toks)
    np.testing.assert_allclose(sound, want[:60], atol=2e-4)
