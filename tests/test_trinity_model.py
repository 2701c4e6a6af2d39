"""The model with sliding-window and full-attention layers in one stack
(K and V of the sliding layers in a RING beside a cache by position), four
norms a block, a leading dense layer and a sigmoid-routed expert layer with
a selection bias and a shared expert, against its plain reference
(perfbench/families/afmoe_reference.py: the only copy), on the CPU at a
small size in float32: hidden 64, 6 heads over 2 KV heads of 16, a window
of 16 in a ring of 24 (the window and a tile of 8), 16 experts of 24 with
2 a token, layers S S F S S with the first dense, contexts to 90.

Three routes meet the reference on LOGITS at lengths under, at and past the
window and past two wraps of the ring (the one-shot forward; prefill by
tiles then decode through the ring; decode rows riding a tile), a tile may
straddle the ring's end, the engine's greedy tokens are the reference's, a
slot reused inherits nothing from the ring's last owner, the ranks' shares
of an expert layer add up to the whole layer with the shared expert counted
once, the bias chooses and does not weigh, the softmax router is bit for
bit what it was, the engine refuses what does not carry a ring, and each
planted fault moves the logits.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import spec, weights
from perfbench.families import afmoe, afmoe_controls, afmoe_reference as ref
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models import TransformerLM
from ray_tpu.models import transformer as tr
from ray_tpu.models.moe import MoEMLP
from ray_tpu.models.transformer import TransformerConfig, cache_shapes

VOCAB = 251
WINDOW, TILE = 16, 8
with open(os.path.join(spec.ROOT, "perfbench", "configs",
                       "trinity-large-preview.json")) as f:
    PUBLISHED = json.load(f)


def config(**over) -> dict:
    """The family's configuration file at the small size: the published
    file with its widths cut, every switch as published, every expert
    held."""
    m = {k: v for k, v in PUBLISHED.items() if k != "reference_tolerance"}
    m.update(hidden_size=64, head_dim=16, num_attention_heads=6,
             num_key_value_heads=2, intermediate_size=96,
             moe_intermediate_size=24, num_experts=16, num_local_experts=16,
             num_experts_per_tok=2, sliding_window=WINDOW, vocab_size=VOCAB,
             max_position_embeddings=512, param_dtype="float32",
             deployment={"expert_rank": 0},
             program={"capacity_factor": 16.0},
             engine=dict(PUBLISHED["engine"], n_slots=3, max_len=96,
                         prefill_chunk=4, prefill_budget=TILE))
    m.update(over)
    return m


def build(m: dict, **over):
    kw = afmoe.model_kwargs(m)
    kw.update(dtype="float32", remat=False, logits_fp32=True, **over)
    return afmoe.build_model(kw)


def seeded(model, seed=0):
    """The family's seeded float32 weights; the norms' scales are drawn
    too, so that each matters, and the bias is made large enough to
    choose."""
    params = weights.seeded_params(model, seed, afmoe.weight_rule)
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
    in a cache, as tests/test_hybrid_mixer_model.py's memo would keep it.)"""
    return jax.jit(lambda params, toks, cache: model.apply(
        {"params": params}, toks, cache=cache, chunked_prefill=chunked))


def through_the_ring(model, params, toks, tile=TILE, ring=None):
    """Logits [L, vocab] of `toks`: prefill by tiles of `tile` (the last
    padded) into a cache laid out as the engine's pools, then, from the
    last whole tile on, one decode row a token."""
    cfg = model.cfg
    if ring:
        model = TransformerLM(dataclasses.replace(cfg, win_ring=ring))
    L = len(toks)
    cache = tr.init_cache(model.cfg, 1, 96 + tile, jnp.float32)
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


# ------------------------------------------------------------ the pools
def test_a_ring_beside_a_cache_by_position_in_one_manager(small):
    _, model, _, _ = small
    shapes = cache_shapes(model.cfg, 3, 96)
    assert shapes == {"k": (1, 3, 96, 2, 16), "v": (1, 3, 96, 2, 16),
                      "wk": (4, 3, 24, 2, 16), "wv": (4, 3, 24, 2, 16)}
    # the ring is as long whatever the cache's length
    assert cache_shapes(model.cfg, 3, 4096)["wk"] == shapes["wk"]
    assert set(tr.CACHE_RINGS) == {"wk", "wv"}


@pytest.mark.parametrize("start", [0, 5, 16, 20, 23, 40, 47])
def test_a_tile_written_into_a_ring_lands_modulo_the_ring(start):
    ring = jnp.arange(2 * 24 * 2 * 3, dtype=jnp.float32).reshape(2, 1, 24, 2,
                                                                 3)
    new = -1.0 - jnp.arange(2 * 8 * 2 * 3, dtype=jnp.float32).reshape(
        2, 1, 8, 2, 3)
    got = np.asarray(jax.jit(tr._ring_write)(ring, new, jnp.int32(start)))
    want = np.array(ring)
    for i in range(8):
        want[:, :, (start + i) % 24] = np.asarray(new)[:, :, i]
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------- against the reference
def test_one_shot_forward_meets_the_reference(small):
    _, model, params, want = small
    got = model.apply({"params": params}, jnp.asarray(tokens(90))[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("L", [
    10,      # under the window
    16,      # at it
    17, 30,  # past it, the ring wrapped once
    49,      # the ring (24) wrapped twice
    90])     # and more
def test_tiles_then_rows_through_the_ring_meet_the_reference(small, L):
    _, model, params, want = small
    got = through_the_ring(model, params, tokens(90)[:L])
    np.testing.assert_allclose(got, want[:L], atol=2e-4)


def test_a_tile_may_straddle_the_rings_end(small):
    """A ring of 28 places is not whole tiles: the tile at position 24
    wraps after four rows."""
    _, model, params, want = small
    got = through_the_ring(model, params, tokens(90)[:70], ring=28)
    np.testing.assert_allclose(got, want[:70], atol=2e-4)


def test_a_ring_too_short_for_tile_and_window_is_refused(small):
    _, model, params, _ = small
    with pytest.raises(ValueError, match="more than the ring"):
        through_the_ring(model, params, tokens(40), ring=20)
    with pytest.raises(ValueError, match="win_ring"):
        InferenceEngine(
            TransformerLM(dataclasses.replace(model.cfg, win_ring=20)),
            params, EngineConfig(n_slots=2, max_len=96, prefill_chunk=4,
                                 prefill_budget=TILE))


def _slots_cache(model, params, lens, toks):
    """A 3-slot pool whose slots hold the first lens[b] of `toks[b]`."""
    cache = tr.init_cache(model.cfg, len(lens), 96, jnp.float32)
    tiled = cached_program(model, True)
    for b, n in enumerate(lens):
        one = tr.init_cache(model.cfg, 1, 96, jnp.float32)
        for at in range(0, n, TILE):
            k = min(TILE, n - at)
            t = np.zeros((1, TILE), np.int32)
            t[0, :k] = toks[b][at:at + k]
            _, one = tiled(params, jnp.asarray(t), dict(
                one, idx=jnp.int32(at), real=(jnp.arange(TILE) < k)[None]))
        for name in cache_shapes(model.cfg, 1, 96):
            cache[name] = cache[name].at[:, b].set(one[name][:, 0])
    return cache


def test_rows_behind_a_tile_give_what_they_give_alone(small):
    """The engine's step: a tile of another prompt and, behind it, one
    decode row a slot at its own length, against rings that have wrapped
    (40), have just filled (24) and have not (9)."""
    m, model, params, _ = small
    lens = [40, 24, 9]
    toks = [tokens(60, seed=10 + b) for b in range(3)]
    slots = _slots_cache(model, params, lens, toks)
    names = tuple(cache_shapes(model.cfg, 1, 96))
    nxt = jnp.asarray([toks[b][n] for b, n in enumerate(lens)], jnp.int32)
    alone, _ = cached_program(model, False)(
        params, nxt[:, None], dict(slots, idx=jnp.asarray(lens, jnp.int32)))
    prompt = tokens(30, seed=20)
    scratch = tr.init_cache(model.cfg, 1, 96 + TILE, jnp.float32)
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
             slots=dict({n: slots[n] for n in names},
                        idx=jnp.asarray(lens, jnp.int32),
                        on=jnp.asarray(True))))
    np.testing.assert_allclose(both[0, TILE:], alone[:, 0], atol=2e-4)
    np.testing.assert_allclose(both[0, :TILE], tile_alone[0], atol=2e-4)
    # and each row was written at its position modulo the ring
    for b, n in enumerate(lens):
        assert float(jnp.abs(new["slots"]["wk"][:, b, n % 24]).sum()) > 0
        np.testing.assert_array_equal(
            np.delete(np.asarray(new["slots"]["wk"][:, b]), n % 24, axis=1),
            np.delete(np.asarray(slots["wk"][:, b]), n % 24, axis=1))


# ----------------------------------------------------------- the engine
def _engine(model, params, **kw):
    cfg = dict(n_slots=3, max_len=96, prefill_chunk=4, prefill_budget=TILE)
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
    """Three requests in flight together, each past the window and one
    past two wraps of its ring while the others' rows ride its tiles."""
    m, model, params, _ = small
    prompts = [tokens(n, seed=30 + n) for n in (57, 21, 35)]
    n_new = [12, 30, 20]
    got = _greedy(_engine(model, params), prompts, n_new)
    for p, n, g in zip(prompts, n_new, got):
        assert len(g) == n and _is_the_references_greedy(params, m, p, g)


def test_a_slot_reused_inherits_nothing_from_the_rings_last_owner(small):
    """One slot: a long request fills and wraps the ring, then a shorter
    one (under the window) takes the slot and gives what a fresh engine
    gives, which is the reference's."""
    m, model, params, _ = small
    long_, short = tokens(70, seed=41), tokens(9, seed=42)
    eng = _engine(model, params, n_slots=1)
    _greedy(eng, [long_], [10])
    again = _greedy(eng, [short], [12])[0]
    fresh = _greedy(_engine(model, params, n_slots=1), [short], [12])[0]
    assert again == fresh and len(again) == 12
    assert _is_the_references_greedy(params, m, short, again)


def test_engine_counts_the_window_and_refuses_what_carries_no_ring(small):
    _, model, params, _ = small
    eng = _engine(model, params)
    _greedy(eng, [tokens(40, seed=50)], [6])
    st = eng.stats()
    assert st["win_pool_bytes"] == 2 * 4 * 3 * 24 * 2 * 16 * 4
    assert st["kv_pool_bytes"] == st["win_pool_bytes"] \
        + 2 * 1 * 3 * 96 * 2 * 16 * 4
    # five decode rows at lengths 40..44: each attends its window of 16
    # and passes over the whole ring (the XLA loop, blocks of 8)
    assert st["win_rows_live"] == 5 * WINDOW
    assert st["win_rows_streamed"] == 5 * 24
    # five tiles of 8 rows hold the prompt's 40: each dispatch sends the
    # five layers' tiles (S S F S S) through `_tile_attention`, and on the
    # CPU the Pallas kernel takes none of them
    assert st["prefill_dispatches"] == 5
    assert st["tile_attn_layers"] == 5 * 5
    assert st["tile_kernel_layers"] == 0
    with pytest.raises(ValueError, match="beyond K and V"):
        _engine(model, params, prefix_cache_slots=2)


# ------------------------------------------------------ the expert layer
def _expert_layer(m, **over):
    """(module, params, x [1, 40, 64]) of one expert layer alone."""
    cfg = build(m, **over).cfg
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64))
    from flax.core import meta
    params = meta.unbox(layer.init(jax.random.PRNGKey(4), x)["params"])
    params["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(5),
                                                    (16,))
    return layer, params, x


def test_ranks_shares_add_up_to_the_whole_layer_shared_counted_once():
    """Eight ranks of two experts each: every rank computes the shared
    expert, so the shares' sum holds it eight times; less seven of it,
    it is the uncut layer, which is the reference's."""
    m = config()
    whole, params, x = _expert_layer(m)
    want, _ = whole.apply({"params": params}, x, exact=True)
    np.testing.assert_allclose(
        want[0], ref.expert_layer(x[0], params, m), atol=2e-5)
    shared_only = ref._fns(m)[5](x[0], *(params[f"shared_{w}"]["kernel"]
                                         for w in ("gate", "up", "down")))
    total = 0.0
    for rank in range(8):
        part = MoEMLP(dataclasses.replace(
            whole.cfg, experts_held=(2 * rank, 2)))
        mine = dict(params, **{w: params[w][2 * rank:2 * rank + 2]
                               for w in ("gate", "up", "down")})
        got, _ = part.apply({"params": mine}, x, exact=True)
        np.testing.assert_allclose(got[0], ref.expert_layer(x[0], mine, dict(
            m, num_local_experts=2, deployment={"expert_rank": rank})),
            atol=2e-5)
        total = total + got[0]
    np.testing.assert_allclose(total - 7 * shared_only, want[0], atol=1e-4)


def test_the_bias_chooses_and_does_not_weigh():
    m = config()
    layer, params, x = _expert_layer(m)
    scores = jax.nn.sigmoid(x[0] @ params["router"])
    taken = lambda p: np.asarray(jax.lax.top_k(  # noqa: E731
        scores + p["router_bias"], 2)[1])
    unbiased = dict(params, router_bias=jnp.zeros((16,)))
    assert (taken(params) != taken(unbiased)).any()
    # with the bias's choice forced on an unbiased layer (a bias so large
    # on the taken experts that it decides alone, on a row of its own),
    # the outputs are the same: the weights are the scores', not the sum's
    got, _ = layer.apply({"params": params}, x, exact=True)
    for t in (0, 7, 23):
        force = jnp.zeros((16,)).at[taken(params)[t]].set(100.0)
        forced, _ = layer.apply(
            {"params": dict(params, router_bias=force)}, x[:, t:t + 1],
            exact=True)
        np.testing.assert_allclose(forced[0, 0], got[0, t], atol=2e-5)
    # and a layer that weighed by score + bias would differ
    with afmoe_controls.planted("bias_weighs", build(m), None) as (mod, _):
        off, _ = MoEMLP(mod.cfg).apply({"params": params}, x, exact=True)
    assert float(jnp.abs(off - got).max()) > 1e-2


# what the parent commit's expert layer gave (softmax over 8 experts, the
# two largest renormalised; float32 on the CPU backend) for the inputs of
# the test below: its first row's first four numbers and its sum
SOFTMAX_WAS = ['-0x1.2c44ac0000000p-7', '-0x1.caf6800000000p-8',
               '0x1.3ca3ce0000000p-6', '-0x1.90c60a0000000p-6',
               '-0x1.00138c0000000p-1', '0x1.04de100000000p+0']


def test_the_softmax_router_is_bit_for_bit_what_it_was():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=48, n_experts=8, expert_top_k=2, capacity_factor=1.25,
        dtype=jnp.float32, param_dtype=jnp.float32)
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 24, 32))
    from flax.core import meta
    params = meta.unbox(layer.init(jax.random.PRNGKey(12), x)["params"])
    assert set(params) == {"router", "gate", "up", "down"}
    out, aux = layer.apply({"params": params}, x)
    got = [float(v).hex() for v in out[0, 0, :4]] + [
        float(out.sum()).hex(), float(aux).hex()]
    assert got == SOFTMAX_WAS


# ------------------------------------------------------ planted faults
@pytest.mark.parametrize("name", [
    n for n in afmoe_controls.CONTROLS
    if n not in ("sound", "matmuls_below_bf16")])
def test_each_planted_fault_moves_the_logits(small, name):
    """The controls of the cell's `correct`, at the small size: past the
    window and a wrap of the ring each one moves the logits by more than
    float32's rounding does."""
    _, model, params, want = small
    toks = tokens(90)[:60]
    m = small[0]
    with afmoe_controls.planted(name, model, params) as (faulty, p):
        got = through_the_ring(faulty, p, toks)
        route = afmoe.route_deviation(p, m, faulty)
    assert float(np.abs(got - want[:60]).max()) > 5e-3
    # the expert layer's own number sees the faults of the expert layer
    # and no other
    assert (route > 1e-3) == (name in ("bias_weighs",
                                       "shared_expert_dropped")), route
    assert afmoe.route_deviation(params, m, model) < 1e-5
    sound = through_the_ring(model, params, toks)
    np.testing.assert_allclose(sound, want[:60], atol=2e-4)
