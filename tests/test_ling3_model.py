"""The model with delta-rule linear attention (a decay a channel, a float32
state a head and a convolution's tail; models/kda.py) in five layers of six
and latent attention in the sixth, a leading dense layer and an expert layer
routed in groups of which the rank holds ONE, against its plain reference
(perfbench/families/ling3_reference.py: the only copy), on the CPU at a
small size in float32: hidden 64, 4 heads of 16 (a latent of 32 + a rope key
of 8), 13 layers by the published rule behind one dense layer (11 "kda", 2
"mla"), 32 experts of 16 in 8 groups with 4 taken and 4 a token, 4 held,
vocabulary 128, chunks of 8 rows in diagonal blocks of 4, contexts to 61.

Routes meet the reference on LOGITS at lengths that end inside, at and past
a tile (the one-shot forward; prefill by tiles then decode through the
caches; decode rows riding a tile) and on the first and last layer's STATE
and TAIL; `kda_scan` over two tiles is one scan and is `kda_step` row by
row, at the decay's bound too; a padded tile hands on what its real rows
made; each kind's pools lie over its own layers; the router in groups is the
reference's by sorting and, with one group, what it was; the eight ranks'
shares add up to the whole layer; the engine's greedy tokens are the
reference's, a slot reused inherits nothing, the engine counts the rows that
reach its share and refuses a prefix cache; and each planted fault moves the
logits.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import spec, weights
from perfbench.families import ling3, ling3_controls, ling3_reference as ref
from ray_tpu.inference import kv_cache
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models import kda, moe
from ray_tpu.models import transformer as tr
from ray_tpu.models.moe import MoEMLP
from ray_tpu.models.transformer import cache_shapes

VOCAB, TILE, MAX_LEN = 128, 8, 104
with open(os.path.join(spec.ROOT, "perfbench", "configs",
                       "ling-3.0-flash-vl.json")) as f:
    PUBLISHED = json.load(f)


def config(**over) -> dict:
    """The family's configuration file at the small size: the published
    file with its widths cut, every switch as published, one group of the
    experts held."""
    m = {k: v for k, v in PUBLISHED.items() if k != "reference_tolerance"}
    m.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             head_dim=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             rotary_dim=8, v_head_dim=16, kv_lora_rank=32,
             intermediate_size=96, moe_intermediate_size=16,
             moe_shared_expert_intermediate_size=16, num_experts=32,
             num_experts_per_tok=4, num_local_experts=4, vocab_size=VOCAB,
             max_position_embeddings=512, param_dtype="float32",
             program={"capacity_factor": 8.0, "kda_chunk": 8, "kda_sub": 4},
             engine=dict(PUBLISHED["engine"], n_slots=3, max_len=MAX_LEN,
                         prefill_chunk=4, prefill_budget=TILE))
    m.update(over)
    return m


def build(m: dict, **over):
    kw = ling3.model_kwargs(m)
    kw.update(dtype="float32", remat=False, logits_fp32=True, **over)
    return ling3.build_model(kw)


def seeded(model, seed=0):
    """The family's seeded float32 weights; every norm's scale is drawn
    too, so that each matters, and the bias is made large enough to
    choose."""
    params = weights.seeded_params(model, seed, ling3.weight_rule)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(treedef, [
        a + 0.2 * jax.random.normal(jax.random.PRNGKey(100 + i), a.shape)
        if path[-1].key in ("scale", "o_norm", "router_bias") else a
        for i, (path, a) in enumerate(leaves)])


def tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         1, VOCAB))


@pytest.fixture(scope="module")
def small():
    """(config dict, model, params, reference logits of 61 tokens)"""
    m = config()
    model = build(m)
    params = seeded(model)
    return m, model, params, np.asarray(ref.logits(params, m, tokens(61)))


def cached_program(model, chunked):
    """(Traced anew each time: a planted fault must not outlive its test
    in a cache.)"""
    return jax.jit(lambda params, toks, cache: model.apply(
        {"params": params}, toks, cache=cache, chunked_prefill=chunked))


def through_the_cache(model, params, toks, tile=TILE):
    """Logits [L, vocab] of `toks`: prefill by tiles of `tile` (the last
    padded) into a cache laid out as the engine's pools, then, from the
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


# ------------------------------------------------------------- the pools
def test_each_kinds_pools_lie_over_its_own_layers(small):
    _, model, _, _ = small
    assert model.cfg.mixer_kinds == ("kda",) * 4 + ("mla",) \
        + ("kda",) * 5 + ("mla",) + ("kda",) * 2
    assert cache_shapes(model.cfg, 3, MAX_LEN) == {
        "lat": (2, 3, 40, MAX_LEN), "s": (11, 3, 4, 16, 16),
        "c": (11, 3, 3, 3 * 4 * 16)}
    assert tr.KIND_CACHES["kda"] == ("s", "c") \
        and tr._STATE_SCOPES["kda"] == (None, "kda", "kda_conv")
    # at the published widths: 576 values a position a latent layer; a
    # state of 32 x 128 x 128 float32 and a tail of 3 x 12,288 a KDA layer
    full = ling3.build_model(ling3.model_kwargs(PUBLISHED)).cfg
    assert cache_shapes(full, 32, 6144) == {
        "lat": (2, 32, 576, 6144), "s": (11, 32, 32, 128, 128),
        "c": (11, 32, 3, 12288)}
    pool = kv_cache.SlotPool(full, 1, 64, 64, 72, jnp.bfloat16)
    assert pool.nbytes(("lat",)) == 2 * 64 * 1152
    assert pool.nbytes(("s",)) == 11 * 32 * 128 * 128 * 4 \
        == 11 * ling3.state_bytes(PUBLISHED)
    assert pool.nbytes(("c",)) == 11 * 3 * 12288 * 4 \
        == 11 * ling3.tail_bytes(PUBLISHED)
    assert tr.decode_rows_read(full, 6144)([]) == {
        "mla_rows_streamed": 0, "mla_rows_live": 0}
    # the two latent layers' tiles alone go by `tile_attention`
    assert tr.tile_attention_layers(full, 1024, 6144 + 1024) == (2, 0)


def test_the_kinds_that_may_stand_together(small):
    _, model, _, _ = small
    kinds = model.cfg.mixer_kinds
    with pytest.raises(ValueError, match="every layer of the stack but"):
        dataclasses.replace(model.cfg, mixer_kinds=("att",) + kinds[1:])
    with pytest.raises(ValueError, match="beside none but"):
        dataclasses.replace(model.cfg, mixer_kinds=("lin",) + ("kda",) * 12)
    with pytest.raises(ValueError, match="whole groups"):
        dataclasses.replace(model.cfg, n_group=5)
    with pytest.raises(ValueError, match="kda_chunk"):
        dataclasses.replace(model.cfg, kda_chunk=6)
    # a block of 32 rows at the bound of -5 decays by exp(-155): its column
    # factors would pass float32
    with pytest.raises(ValueError, match="80 nats"):
        dataclasses.replace(model.cfg, kda_chunk=64, kda_sub=32)


# ------------------------------------------------- against the reference
def test_one_shot_forward_meets_the_reference(small):
    _, model, params, want = small
    got = model.apply({"params": params}, jnp.asarray(tokens(61))[None])[0]
    np.testing.assert_allclose(got, want, atol=3e-4)


@pytest.mark.parametrize("L", [
    5,       # inside the first tile
    8,       # at its end
    9,       # past it
    16, 17,  # at and past the second
    30,      # tiles that hand states and tails on
    61])     # and more, decode rows behind them
def test_tiles_then_rows_through_the_caches_meet_the_reference(small, L):
    _, model, params, want = small
    got = through_the_cache(model, params, tokens(61)[:L])
    np.testing.assert_allclose(got, want[:L], atol=3e-4)


def test_the_pools_hold_the_references_states_and_tails(small):
    """The family's own comparison at the small size (`program_rows`: tiles
    into a scratch, the scratch made a slot, rows out of it): the first and
    the last KDA layer's state and tail against the reference's, after
    `insert` and after the last scored token; the logits; the first expert
    layer."""
    m, model, params, _ = small
    toks = tokens(50)
    m = dict(m, reference_tolerance=dict(
        logit_gap=0.1, logit_rms=1e-4, state_rel=1e-4, state_rel_last=1e-4,
        tail_rel=1e-5, tail_rel_last=1e-4, latent_rel=1e-4, route_rel=1e-5))
    got = ling3.program_rows(params, m, toks[:29], toks[29:], model=model)
    assert got["states"].shape == (2, 11, 4, 16, 16) \
        and got["tails"].shape == (2, 11, 3, 192) \
        and got["latents"].shape == (49, 40) and got["edge"] == [
            8, 9, 10, 16, 17, 18, 24, 25, 26]
    score = ling3.scored(params, m, toks[:29], toks[29:], program=got)
    assert score["logit_rms"] < 1e-4 and score["edge_rms"] < 1e-4
    assert all(score[k] < m["reference_tolerance"][k]
               for k in ling3.NUMBERS), score
    assert ling3.folded(score, m["reference_tolerance"]) == score["gaps"]
    # a number over its limit counts every token of the case
    over = ling3.folded(dict(score, tail_rel_last=3e-4),
                        m["reference_tolerance"])
    assert min(over) >= 0.1 * 3 - 1e-9
    # and a number that is not finite is over every limit
    lost = ling3.folded(dict(score, state_rel=float("nan")),
                        m["reference_tolerance"])
    assert min(lost) > 1e3


# ------------------------------------------------------- the recurrence
def _rows(T, seed=3, B=2, H=3, D=16, bound=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda.unit(jax.random.normal(ks[0], (B, T, H, D)), D ** -0.5)
    k = kda.unit(jax.random.normal(ks[1], (B, T, H, D)))
    v = jax.random.normal(ks[2], (B, T, H, D))
    g = -5.0 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], (B, T, H, D)))
    if bound:                   # at the lower bound in every channel
        g = jnp.full_like(g, -5.0 * (1 - 1e-6))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, H, D, D))


def _by_steps(rows, S):
    out = []
    for t in range(rows[0].shape[1]):
        o, S = kda.kda_step(*(a[:, t:t + 1] for a in rows), S)
        out.append(o)
    return jnp.concatenate(out, 1), S


@pytest.mark.parametrize("chunk,sub", [(8, 4), (16, 4), (64, 16), (16, 16)])
def test_a_scan_over_two_tiles_is_one_scan_and_the_step_row_by_row(chunk,
                                                                   sub):
    rows, S0 = _rows(75)
    whole, S = kda.kda_scan(*rows, S0, chunk=chunk, sub=sub)
    first, S1 = kda.kda_scan(*(a[:, :40] for a in rows), S0, chunk=chunk,
                             sub=sub)
    second, S2 = kda.kda_scan(*(a[:, 40:] for a in rows), S1, chunk=chunk,
                              sub=sub)
    stepped, St = _by_steps(rows, S0)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=2e-5)
    np.testing.assert_allclose(whole, stepped, atol=2e-5)
    np.testing.assert_allclose(S2, S, atol=2e-5)
    np.testing.assert_allclose(S, St, atol=2e-5)


def test_a_chunk_at_the_decays_bound_stays_finite_and_right():
    """64 rows of g = -5 a channel: the chunk's cumulated exponent reaches
    -320, and exp(+320) is never formed."""
    rows, S0 = _rows(64, seed=5, bound=True)
    out, S = kda.kda_scan(*rows, S0, chunk=64, sub=16)
    stepped, St = _by_steps(rows, S0)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(S).all())
    np.testing.assert_allclose(out, stepped, atol=2e-5)
    np.testing.assert_allclose(S, St, atol=2e-5)
    # and keys all alike, hardly decaying: the inverse's entries cancel
    # nowhere (the series sum (-A)^j would lose them)
    q, k, v, g, beta = rows
    same = (q, jnp.broadcast_to(k[:, :1], k.shape), v, g * 1e-4,
            jnp.ones_like(beta))
    out, S = kda.kda_scan(*same, S0, chunk=64, sub=16)
    stepped, St = _by_steps(same, S0)
    np.testing.assert_allclose(out, stepped, atol=1e-4)
    np.testing.assert_allclose(S, St, atol=1e-4)


def test_a_padded_tile_hands_on_what_its_real_rows_made(small):
    """Through the model's first layers: a tile of 8 with 5 real rows leaves
    states and tails as the 5 rows alone do, and a row no request owns
    leaves a slot's as they were."""
    _, model, params, _ = small
    toks = tokens(8)
    tiled = cached_program(model, True)
    fresh = tr.init_cache(model.cfg, 1, MAX_LEN, jnp.float32)
    _, padded = tiled(params, jnp.asarray(toks)[None], dict(
        fresh, idx=jnp.int32(0), real=(jnp.arange(8) < 5)[None]))
    short = jax.jit(lambda p, t, c: model.apply(
        {"params": p}, t, cache=c, chunked_prefill=True))
    _, alone = short(params, jnp.asarray(toks[:5])[None],
                     dict(fresh, idx=jnp.int32(0)))
    for n in ("s", "c"):
        assert float(jnp.abs(alone[n]).max()) > 0
        np.testing.assert_allclose(padded[n], alone[n], atol=2e-5)
    rows, S0 = _rows(1)
    _, S = kda.kda_step(*rows, S0, real=jnp.asarray([True, False]))
    assert bool((S[1] == S0[1]).all()) and not bool((S[0] == S0[0]).all())


def test_a_rows_convolution_is_the_tiles_on_one_row():
    """`conv_row` (one row a slot, no gather) against `ssm.causal_conv`:
    the same output and the same tail, and a row no request owns leaves the
    tail as it was."""
    from ray_tpu.models import ssm
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    x = jax.random.normal(ks[0], (3, 1, 24))
    tail = jax.random.normal(ks[1], (3, 3, 24))
    w = jax.random.normal(ks[2], (4, 24))
    real = jnp.asarray([[True], [False], [True]])
    y, new = kda.conv_row(x, tail, w, None, real)
    want_y, want_new = ssm.causal_conv(x, tail, w, None, real)
    np.testing.assert_allclose(y, want_y, atol=1e-6)
    np.testing.assert_array_equal(new, want_new)
    np.testing.assert_array_equal(new[1], tail[1])


# ------------------------------------------------------------ the router
def _todays_sigmoid_route(x, router, bias, k):
    """`moe.sigmoid_route` as it stood before the groups."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router)
    _, taken = jax.lax.top_k(scores + bias, k)
    return scores, jnp.take_along_axis(scores, taken, axis=-1), taken


def test_with_one_group_the_router_is_todays_bit_for_bit():
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(ks[0], (2, 40, 64))
    router = jax.random.normal(ks[1], (64, 32)) / 8
    bias = 0.3 * jax.random.normal(ks[2], (32,))
    new = jax.jit(lambda *a: moe.sigmoid_route(*a, 4))(x, router, bias)
    old = jax.jit(lambda *a: _todays_sigmoid_route(*a, 4))(x, router, bias)
    for a, b in zip(new, old):
        np.testing.assert_array_equal(a, b)
    assert jax.jit(lambda *a: moe.sigmoid_route(*a, 4)).lower(
        x, router, bias).as_text() == jax.jit(
        lambda *a: _todays_sigmoid_route(*a, 4)).lower(
        x, router, bias).as_text()


def test_the_router_in_groups_is_the_references_by_sorting(small):
    m, model, params, _ = small
    p = params["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 200, 64))
    _, vals, taken = moe.sigmoid_route(x, p["router"], p["router_bias"], 4,
                                       8, 4)
    # every row's picks lie in at most four groups of four neighbours
    assert all(len({int(e) // 4 for e in row}) <= 4
               for row in np.asarray(taken[0]))
    # some row's best eight of all experts are NOT its picks: the groups
    # bind
    _, _, free = moe.sigmoid_route(x, p["router"], p["router_bias"], 4)
    assert (np.sort(np.asarray(free[0])) != np.sort(
        np.asarray(taken[0]))).any()
    w = vals / vals.sum(-1, keepdims=True) * 2.5
    got = jnp.einsum("lk,lke->le", w[0], jax.nn.one_hot(taken[0], 32))
    gates = ref._fns(dict(m, num_local_experts=32,
                          deployment={"expert_rank": 0}))[-2]
    np.testing.assert_allclose(
        got, gates(x[0], p["router"], p["router_bias"]), atol=1e-6)


def test_ranks_shares_add_up_to_the_whole_layer_shared_counted_once():
    """Eight ranks of one group each, four picks a token out of four
    groups, the gates times 2.5: every rank computes the shared expert, so
    the shares' sum holds it eight times; less seven of it, it is the uncut
    layer, which is the reference's."""
    m = config(num_local_experts=32, deployment={"expert_rank": 0,
                                                 "stage_layers": [1]})
    cfg = build(m).cfg
    assert (cfg.expert_top_k, cfg.route_scale, cfg.router, cfg.n_group,
            cfg.topk_group) == (4, 2.5, "sigmoid", 8, 4)
    whole = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64))
    from flax.core import meta
    params = meta.unbox(whole.init(jax.random.PRNGKey(4), x)["params"])
    params["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(5),
                                                    (32,))
    want, _ = whole.apply({"params": params}, x, exact=True)
    np.testing.assert_allclose(
        want[0], ref.expert_layer(x[0], params, m), atol=2e-5)
    shared_only = ref._fns(m)[-3](x[0], *(params[f"shared_{w}"]["kernel"]
                                          for w in ("gate", "up", "down")))
    total = 0.0
    for rank in range(8):
        part = MoEMLP(dataclasses.replace(cfg, experts_held=(4 * rank, 4)))
        mine = dict(params, **{w: params[w][4 * rank:4 * rank + 4]
                               for w in ("gate", "up", "down")})
        got, _ = part.apply({"params": mine}, x, exact=True)
        np.testing.assert_allclose(got[0], ref.expert_layer(x[0], mine, dict(
            m, num_local_experts=4, deployment={"expert_rank": rank})),
            atol=2e-5)
        total = total + got[0]
    np.testing.assert_allclose(total - 7 * shared_only, want[0], atol=1e-4)


# ------------------------------------------------ rows riding a tile
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
        for name in ("lat", "s", "c"):
            cache[name] = cache[name].at[:, b].set(one[name][:, 0])
    return cache


def test_rows_behind_a_tile_give_what_they_give_alone(small):
    """The engine's step: a tile of another prompt and, behind it, one
    decode row a slot at its own length, one of them a row no request owns:
    its state and tail stay as they were."""
    _, model, params, _ = small
    lens = [40, 24, 9]
    toks = [tokens(60, seed=10 + b) for b in range(3)]
    slots = _slots_cache(model, params, lens, toks)
    nxt = jnp.asarray([toks[b][n] for b, n in enumerate(lens)], jnp.int32)
    alone, after = cached_program(model, False)(
        params, nxt[:, None], dict(slots, idx=jnp.asarray(lens, jnp.int32)))
    prompt = tokens(30, seed=20)
    scratch = tr.init_cache(model.cfg, 1, MAX_LEN + TILE, jnp.float32)
    tiled = cached_program(model, True)
    for at in (0, 8, 16):
        tile_alone, moved = tiled(params, jnp.asarray(
            prompt[at:at + TILE])[None], dict(scratch, idx=jnp.int32(at)))
        if at < 16:
            scratch = moved
    live = jnp.asarray([True, True, False])
    both, new = tiled(
        params, jnp.concatenate([jnp.asarray(prompt[16:24]), nxt])[None],
        dict(scratch, idx=jnp.int32(16),
             real=jnp.concatenate([jnp.ones((TILE,), bool), live])[None],
             slots=dict({n: slots[n] for n in ("lat", "s", "c")},
                        idx=jnp.asarray(lens, jnp.int32),
                        on=jnp.asarray(True))))
    np.testing.assert_allclose(both[0, TILE:TILE + 2], alone[:2, 0],
                               atol=3e-4)
    np.testing.assert_allclose(both[0, :TILE], tile_alone[0], atol=3e-4)
    for n in ("s", "c"):
        np.testing.assert_allclose(new["slots"][n][:, :2], after[n][:, :2],
                                   atol=1e-5)
        np.testing.assert_array_equal(new["slots"][n][:, 2], slots[n][:, 2])
        np.testing.assert_allclose(new[n], moved[n], atol=1e-5)


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
    return len(gaps) == len(generated) and max(gaps) < 2e-3


def test_engine_greedy_tokens_are_the_references(small):
    """Three requests in flight together, the others' rows riding each
    one's tiles."""
    m, model, params, _ = small
    prompts = [tokens(n, seed=30 + n) for n in (57, 21, 35)]
    n_new = [12, 30, 20]
    got = _greedy(_engine(model, params), prompts, n_new)
    for p, n, g in zip(prompts, n_new, got):
        assert len(g) == n and _is_the_references_greedy(params, m, p, g)


def test_a_slot_reused_inherits_nothing_from_its_last_owner(small):
    """One slot: a long request fills it, then a shorter one takes it and
    gives what a fresh engine gives, which is the reference's."""
    m, model, params, _ = small
    long_, short = tokens(70, seed=41), tokens(9, seed=42)
    eng = _engine(model, params, n_slots=1)
    _greedy(eng, [long_], [10])
    again = _greedy(eng, [short], [12])[0]
    fresh = _greedy(_engine(model, params, n_slots=1), [short], [12])[0]
    assert again == fresh and len(again) == 12
    assert _is_the_references_greedy(params, m, short, again)


def test_engine_counts_its_pools_its_hits_and_refuses_a_prefix_cache(small):
    m, model, params, _ = small
    eng = _engine(model, params)
    prompt = tokens(40, seed=50)
    _greedy(eng, [prompt], [6])
    st = eng.stats()
    assert st["latent_pool_bytes"] == 2 * 3 * MAX_LEN * 40 * 4
    assert st["state_pool_bytes"] == 11 * 3 * 4 * 16 * 16 * 4
    assert st["conv_pool_bytes"] == 11 * 3 * 3 * 192 * 4
    assert st["kv_pool_bytes"] == st["latent_pool_bytes"] \
        + st["state_pool_bytes"] + st["conv_pool_bytes"]
    assert st["mla_rows_live"] == sum(n + 1 for n in range(40, 45))
    # five tiles of 8 rows hold the prompt's 40: the two latent layers'
    # tiles go through the blocked loop
    assert st["prefill_dispatches"] == 5 and st["tile_attn_layers"] == 10 \
        and st["tile_kernel_layers"] == 0
    # the prompt's 40 rows and five decode steps' three rows each (a
    # decode-only step routes every slot's row, live or not, as it counts
    # their picks) passed each of the 12 expert layers; those that took an
    # expert of the held group are about half at most and more than none,
    # and each took four picks at most
    assert st["moe_rows_real"] == 12 * (40 + 5 * 3)
    assert 0 < st["moe_rows_hit"] < st["moe_rows_real"] // 2 + 12 * 5
    assert st["moe_rows_hit"] <= st["moe_local_picks"] \
        <= 4 * st["moe_rows_hit"]
    # a model that routes without groups counts rows and picks alone
    free = build(dict(m, n_group=1, topk_group=1))
    assert not moe.counts_hits(free.cfg) and moe.counts_hits(model.cfg)
    with pytest.raises(ValueError, match="beyond K and V"):
        _engine(model, params, prefix_cache_slots=2)


# ------------------------------------------------------ planted faults
@pytest.mark.parametrize("name", [
    n for n in ling3_controls.CONTROLS if n != "sound"])
def test_each_planted_fault_moves_the_logits(small, name):
    """The controls of the cell's `correct`, at the small size: each one
    moves the logits by more than float32's rounding does, through tiles
    and rows."""
    m, model, params, want = small
    toks = tokens(61)[:45]
    with ling3_controls.planted(name, model, params) as (faulty, p):
        got = through_the_cache(faulty, p, toks)
        route = ling3.route_deviation(p, m, faulty)
    off = np.abs(got - want[:45]).max(-1)
    assert off.max() > 5e-3
    if name == "state_in_bf16":
        # a first tile from a zero state rounds what it hands on alone
        assert off[:TILE].max() < 3e-4
    if name in ("state_zeroed_at_tile_start", "tail_zeroed_at_tile_start"):
        # the first tile is sound: it starts from nothing either way
        assert off[:TILE].max() < 3e-4 < off[TILE:].max()
    # the expert layer's own number sees the faults of the expert layer
    # and no other (float32 arithmetic on whatever weights it is given)
    routed = name in ("no_groups", "group_scored_by_its_largest",
                      "bias_weighs", "scale_left_out")
    assert (route > 1e-3) == routed, route
