"""The expert layer's grouped form (models/moe.py `_grouped`,
ops/grouped_matmul.py) on the CPU at float32: against the dense dispatch
on the same weights (the same module with `exact` False, which at
C == L drops nothing either), the kernel interpreted against its XLA form,
the sorted layout itself, and the predicate that chooses the form.

The router is steered through one feature: every row's first value is 1
and the router's first row holds a bias an expert, so that an expert can
be left out or handed most of the rows.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.moe import MoEMLP, rows_follow_routing, takes_grouped
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops import grouped_matmul as gm

D, F = 32, 64


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=D, n_layers=1, n_heads=2,
                n_kv_heads=2, d_ff=F, n_experts=8, expert_top_k=2,
                capacity_factor=4.0, dtype=jnp.float32,
                param_dtype=jnp.float32)
    return TransformerConfig(**dict(base, **kw))


def _layer(cfg, L, bias=None, seed=0):
    """(module, variables, x [1, L, D]): the router's first row is `bias`
    (an expert's logit, the rows' first value being 1)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, L, D)) * 0.3
    x = x.at[:, :, 0].set(1.0)
    layer = MoEMLP(cfg)
    v = jax.tree.map(lambda a: a, layer.init(jax.random.PRNGKey(seed + 1),
                                             x))
    v = {"params": dict(v["params"])}
    if bias is not None:
        router = v["params"]["router"]
        v["params"]["router"] = router.replace_boxed(
            router.unbox().at[0].set(jnp.asarray(bias, jnp.float32)))
    return layer, v, x


def _both(layer, v, x, real=None, tail=0):
    """((out, rows_and_picks) of the grouped form, of the dense one)."""
    got = []
    for exact in (True, False):
        (out, _), sown = layer.apply(v, x, real=real, exact=exact,
                                     tail=tail, mutable=["counters"])
        (pair,) = sown["counters"]["rows_and_picks"]
        got.append((np.asarray(out), np.asarray(pair)))
    return got


def _picks(layer, v, x):
    """[L, K] the experts the router takes, recomputed here."""
    router = v["params"]["router"].unbox()
    cfg = layer.cfg
    scores = x[0].astype(jnp.float32) @ router
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(scores) + v["params"]["router_bias"].unbox()
    return np.asarray(jax.lax.top_k(scores, cfg.expert_top_k)[1])


def _live_rows(picks, valid, cfg, L):
    """The rows the grouped form computes: each held expert's picks
    rounded up to whole tiles."""
    first, held = cfg.experts_held or (0, cfg.n_experts)
    tile = gm.row_tile(L, cfg.expert_top_k, cfg.n_experts)
    counts = [int(((picks == first + e) & valid).sum()) for e in range(held)]
    return sum(-(-c // tile) * tile for c in counts), sum(counts)


FORMS = {
    # name: (config's changes, L, the router's bias, rows not real, tail)
    "every-expert-hit": ({}, 40, None, (), 0),
    "an-expert-takes-no-row": ({}, 40, [0, 0, 0, -30, 0, 0, 0, 0], (), 0),
    "an-expert-takes-three-tiles": (
        {}, 40, [30, 0, 0, 0, 0, 0, 0, 0], (), 0),
    "padded-tail-and-idle-slots": (
        {}, 48, None, tuple(range(25, 32)) + (34, 35, 41), 16),
    "tail-16-behind-a-tile": ({}, 48, None, (), 16),
    "sigmoid-router-and-shared-expert": (
        dict(router="sigmoid", n_shared_experts=1, n_experts=16,
             capacity_factor=8.0), 32, None, (5,), 0),
    "a-held-share": (dict(experts_held=(2, 4)), 40, None, (3, 39), 8),
    "a-held-share-nobody-picks": (
        dict(experts_held=(2, 4)), 40, [0, 0, -30, -30, -30, -30, 0, 0], (),
        0),
}


@pytest.mark.parametrize("name", list(FORMS))
def test_grouped_form_is_the_dense_dispatch(name):
    changes, L, bias, unreal, tail = FORMS[name]
    cfg = _cfg(**changes)
    assert takes_grouped(cfg, L) and not takes_grouped(cfg, L, exact=False)
    layer, v, x = _layer(cfg, L, bias)
    real = np.ones((1, L), bool)
    real[0, list(unreal)] = False
    (got, pair), (want, dense_pair) = _both(layer, v, x, jnp.asarray(real),
                                            tail)
    np.testing.assert_allclose(got[0, real[0]], want[0, real[0]],
                               atol=1e-5, rtol=1e-5)
    picks = _picks(layer, v, x)
    rows, n = _live_rows(picks, real[0][:, None], cfg, L)
    assert tuple(pair) == (rows, n)
    held = (cfg.experts_held or (0, cfg.n_experts))[1]
    assert tuple(dense_pair) == (held * L, n) and rows < held * L
    if name == "an-expert-takes-three-tiles":
        tile = gm.row_tile(L, 2, 8)
        assert (picks == 0).sum() == L and L == 2.5 * tile
    if name == "an-expert-takes-no-row":
        assert not (picks == 3).any()
    if name == "a-held-share-nobody-picks":
        assert tuple(pair) == (0, 0) and not got.any()


def test_rows_no_request_owns_are_routed_nowhere():
    """A padded tail's rows and an idle slot's row cost no row, their
    routed result is zero, and what they hold moves nothing."""
    cfg, L, tail = _cfg(), 48, 16
    layer, v, x = _layer(cfg, L)
    real = np.ones((1, L), bool)
    real[0, 20:32] = False
    real[0, [33, 40]] = False
    (a, pair_a), _ = _both(layer, v, x, jnp.asarray(real), tail)
    noise = x.at[:, 20:32].set(7.0).at[:, 33].set(-3.0)
    (b, pair_b), _ = _both(layer, v, noise, jnp.asarray(real), tail)
    assert (a[0, real[0]] == b[0, real[0]]).all()
    assert not a[0, ~real[0]].any() and tuple(pair_a) == tuple(pair_b)


def test_a_tiles_result_is_the_same_bits_whatever_rides_behind_it():
    """PR 28's rule: the tile's picks lie before the tail's in every
    expert's rows, so they sit at the same place of the same row tile
    whatever the rows behind the tile hold or pick."""
    cfg, L, tail = _cfg(), 48, 16
    layer, v, x = _layer(cfg, L)
    other = x.at[:, L - tail:].set(
        jax.random.normal(jax.random.PRNGKey(9), (1, tail, D)))
    real = jnp.ones((1, L), bool)
    idle = real.at[:, L - tail:].set(False)
    (a, _), _ = _both(layer, v, x, real, tail)
    (b, _), _ = _both(layer, v, other, real, tail)
    (c, _), _ = _both(layer, v, other, idle, tail)
    assert (a[0, :L - tail] == b[0, :L - tail]).all()
    assert (a[0, :L - tail] == c[0, :L - tail]).all()


def test_several_groups_are_sorted_as_one():
    cfg, L = _cfg(), 40
    layer, v, x = _layer(cfg, L)
    x3 = jnp.concatenate([x, x[:, ::-1], x * 0.5])
    (got, _), (want, _) = _both(layer, v, x3)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


PREDICATE = {
    # name: (config's changes, L, exact, taken)
    "mixtral-step": ({}, 272, True, True),
    "mixtral-128-row-tile": ({}, 144, True, True),
    "one-row-a-group": ({}, 1, True, False),
    "a-few-rows": ({}, 8, True, False),
    "training": ({}, 272, False, False),
    "capacity-under-the-group": (
        dict(n_experts=128, expert_top_k=8, experts_held=(0, 16),
             capacity_factor=2.0), 1040, True, False),
    "four-experts-top-2": (dict(n_experts=4, capacity_factor=2.0), 24,
                           True, False),
}


@pytest.mark.parametrize("name", list(PREDICATE))
def test_the_form_follows_the_shapes(name):
    changes, L, exact, taken = PREDICATE[name]
    assert takes_grouped(_cfg(**changes), L, exact) is taken


def test_the_engine_reads_counts_where_the_rows_follow_the_routing():
    assert rows_follow_routing(_cfg())                   # C == L always
    assert rows_follow_routing(_cfg(capacity_factor=1.5,
                                    experts_held=(2, 4)))
    assert not rows_follow_routing(_cfg(capacity_factor=1.5))
    assert not rows_follow_routing(_cfg(n_experts=0))


def test_mixtrals_step_bound():
    """ISSUE 55's arithmetic: 544 picks and 8 x 127 rows of padding are
    1,560 rows, 13 tiles of 128, against the dense form's 2,176."""
    assert gm.row_tile(272, 2, 8) == 128 and 544 + 8 * 127 < 8 * 272
    # the layout: an expert's rows from a boundary of two tiles, 11 spans
    assert gm.rows_bound(544, 8, 2 * 128) == 2816
    assert gm.fits(272, 4096, 14336, 128, jnp.bfloat16)
    assert gm.block_f(4096, 14336, 2) == 1024
    # half a sublane tile; no whole lane tiles; rows that fill the VMEM
    assert not gm.fits(272, 4096, 14336, 8, jnp.bfloat16)
    assert not gm.fits(272, 96, 14336, 128, jnp.bfloat16)
    assert not gm.fits(35, 4096, 14336, 128, jnp.bfloat16)
    assert not gm.fits(4096, 4096, 14336, 128, jnp.bfloat16)


def test_no_grouped_form_under_a_mesh_that_shards():
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.mesh import use_mesh
    with use_mesh(make_mesh(MeshConfig(data=1, fsdp=2, expert=2, seq=1,
                                       tensor=2))):
        assert not takes_grouped(_cfg(), 272)
    assert takes_grouped(_cfg(), 272)


# ------------------------------------------------------------ the layout
def _layout(seed, N, K, E, span, late=0, p_valid=0.8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    expert = jax.random.randint(ks[0], (N, K), 0, E)
    valid = jax.random.uniform(ks[1], (N, K)) < p_valid
    is_late = jnp.arange(N) >= N - late
    out = gm.sort_picks(expert, valid, E, span, is_late if late else None)
    return (np.asarray(expert), np.asarray(valid), np.asarray(is_late),
            *map(np.asarray, out))


@pytest.mark.parametrize("seed,N,K,E,tile,late", [
    (0, 40, 2, 8, 16, 0), (1, 48, 2, 8, 16, 16), (2, 33, 3, 4, 8, 5),
    (3, 272, 2, 8, 256, 16)])
def test_sorted_layout(seed, N, K, E, tile, late):
    """(`tile` here: the rows of a span, the layout's boundary.)"""
    expert, valid, is_late, tile_expert, rows, n_live, src, slot = _layout(
        seed, N, K, E, tile, late)
    R = gm.rows_bound(N * K, E, tile)
    assert src.shape == (R,) and tile_expert.shape == (R // tile,)
    counts = [(valid & (expert == e)).sum() for e in range(E)]
    assert n_live[0] == sum(-(-c // tile) for c in counts)
    assert rows.shape == tile_expert.shape and rows.sum() == sum(counts)
    assert (rows == [(src[i * tile:(i + 1) * tile] >= 0).sum()
                     for i in range(R // tile)]).all()
    # every valid pick lies once, at its slot, in a tile of its expert
    flat = np.flatnonzero(valid.reshape(-1))
    assert sorted(src[src >= 0]) == list(flat)
    assert (src[slot.reshape(-1)[flat]] == flat).all()
    assert (tile_expert[slot.reshape(-1)[flat] // tile]
            == expert.reshape(-1)[flat]).all()
    # an expert's rows start on a tile boundary and run in the order
    # (late, row, k); past the live tiles, the last live one's expert
    at = 0
    for e in range(E):
        mine = src[at * tile:(at + -(-counts[e] // tile)) * tile]
        held = mine[mine >= 0]
        assert len(held) == counts[e] and (mine[:counts[e]] >= 0).all()
        key = [(is_late[p // K], p) for p in held]
        assert key == sorted(key)
        at += -(-counts[e] // tile)
    assert at == n_live[0] and (src[at * tile:] == -1).all()
    if at:
        assert (tile_expert[at:] == tile_expert[at - 1]).all()


def test_no_valid_pick_is_no_live_tile():
    *_, rows, n_live, src, slot = _layout(0, 24, 2, 8, 8, p_valid=0.0)
    assert n_live[0] == 0 and (src == -1).all() and not slot.any()
    assert not rows.any()


# ------------------------------------------------------------ the kernel
def _weights(E, d, f, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    draw = lambda k, s, n: (jax.random.normal(k, s) * n ** -0.5  # noqa: E731
                            ).astype(dtype)
    return (draw(ks[0], (E, d, f), d), draw(ks[1], (E, d, f), d),
            draw(ks[2], (E, f, d), f))


KERNEL = {
    # name: (dtype, tile, the experts' counts of rows, bf, tolerance)
    "float32": (jnp.float32, 8, (8, 3, 0, 37, 1), 128, 1e-5),
    "float32-one-block": (jnp.float32, 16, (5, 0, 0, 40), None, 1e-5),
    "bfloat16": (jnp.bfloat16, 16, (16, 1, 0, 65, 7), 128, 0.0),
    "nothing-live": (jnp.float32, 8, (0, 0, 0), 128, 0.0),
}


def _routed(counts, dtype, tile, K=2, d=128, seed=5):
    """(x [N, d], experts [N, K], valid, gates, `sort_picks`' results): N
    rows of K picks, the first pick's experts in `counts`' numbers, the
    second's the next expert's, every fourth row's second pick not
    computed."""
    E = len(counts)
    first = np.repeat(np.arange(E), counts)
    N = -(-max(len(first), 1) // 16) * 16
    expert = np.zeros((N, K), np.int32)
    expert[:len(first), 0] = first
    expert[:len(first), 1] = (first + 1) % E
    valid = np.zeros((N, K), bool)
    valid[:len(first)] = True
    valid[::4, 1] = False
    if not sum(counts):
        valid[:] = False
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = (jax.random.normal(ks[0], (N, d)) * 0.5).astype(dtype)
    gates = jnp.where(valid, jax.random.uniform(ks[1], (N, K)), 0.0)
    gates = gates.astype(dtype).astype(jnp.float32)
    layout = gm.sort_picks(jnp.asarray(expert), jnp.asarray(valid), E,
                           gm.SPAN * tile)
    return x, expert, valid, gates, layout


@pytest.mark.parametrize("name", list(KERNEL))
def test_kernel_interpreted_against_its_xla_form_and_plain_matmuls(name):
    dtype, tile, counts, bf, tol = KERNEL[name]
    E, d, f = len(counts), 128, 256
    wg, wu, wd = _weights(E, d, f, dtype)
    x, expert, valid, gates, layout = _routed(counts, dtype, tile)
    got = gm.routed_swiglu(x, wg, wu, wd, *layout, gates, tile=tile, bf=bf,
                           interpret=True)
    want = gm.routed_swiglu_reference(x, wg, wu, wd, *layout, gates,
                                      tile=tile, bf=bf)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))        # noqa: E731
    np.testing.assert_allclose(f32(got), f32(want), atol=tol)
    # a tile that holds no row is not computed
    computed = [(valid & (expert == e)).sum() for e in range(E)]
    assert int(gm.rows_computed(layout[1], tile)) == sum(
        -(-c // tile) * tile for c in computed)
    if not sum(counts):
        assert not f32(got).any()
    if dtype != jnp.float32:
        return
    plain = np.zeros(x.shape, np.float32)
    for n, k in zip(*np.nonzero(valid)):
        e = int(expert[n, k])
        plain[n] += float(gates[n, k]) * np.asarray(
            (jax.nn.silu(x[n] @ wg[e]) * (x[n] @ wu[e])) @ wd[e])
    np.testing.assert_allclose(f32(got), plain, atol=1e-5, rtol=1e-5)


def test_kernel_reads_a_layer_of_a_stack_in_place():
    """Under a scan the kernel takes every layer's experts as one stack
    and a table that counts from the layer's first expert."""
    E, d, f, tile, n_layers = 3, 128, 256, 8, 3
    stacks = [_weights(E, d, f, jnp.float32, seed=i) for i in range(n_layers)]
    whole = [jnp.concatenate([s[i] for s in stacks]) for i in range(3)]
    x, _, _, gates, (span_expert, *layout) = _routed((4, 0, 9), jnp.float32,
                                                     tile)
    for layer in range(n_layers):
        alone = gm.routed_swiglu(x, *stacks[layer], span_expert, *layout,
                                 gates, tile=tile, interpret=True)
        stacked = gm.routed_swiglu(x, *whole, span_expert + layer * E,
                                   *layout, gates, tile=tile, interpret=True)
        assert (np.asarray(alone) == np.asarray(stacked)).all()
        assert np.asarray(alone).any()


def test_kernel_refuses_what_does_not_tile():
    wg, wu, wd = _weights(2, 96, 256, jnp.float32)
    x, _, _, gates, layout = _routed((3, 2), jnp.float32, 8, d=96)
    with pytest.raises(ValueError, match="whole lane"):
        gm.routed_swiglu(x, wg, wu, wd, *layout, gates, tile=8,
                         interpret=True)


# ------------------------------------------------- through the model's scan
@pytest.mark.parametrize("scan", [True, False])
def test_the_cached_forward_hands_the_scan_its_experts_whole(scan,
                                                             monkeypatch):
    """The scanned serving forward passes the layers' expert weights whole
    beside the layers' numbers (`TransformerLM._stacked_experts`), and the
    grouped form reads layer i's experts at `i * E`: the logits are those
    of the layer-by-layer stack on the same weights."""
    from flax.core import meta

    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import init_cache
    cfg = dataclasses.replace(
        _cfg(), n_layers=3, max_seq_len=64, scan_layers=scan)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 40), 0, 64)
    seen = []
    xla_form = gm.routed_swiglu_reference
    monkeypatch.setattr(
        gm, "routed_swiglu_reference",
        lambda rows, wg, *a, **kw: seen.append(wg.shape) or xla_form(
            rows, wg, *a, **kw))
    scanned = TransformerLM(dataclasses.replace(cfg, scan_layers=True))
    params = meta.unbox(scanned.init(jax.random.PRNGKey(0), toks)["params"])
    if not scan:
        block = params.pop("layers")["block"]
        params.update({f"layer_{i}": jax.tree.map(lambda a: a[i], block)
                       for i in range(3)})
    logits, _ = model.apply({"params": params}, toks,
                            cache=init_cache(cfg, 1, 64))
    want = model.apply({"params": params}, toks)
    assert set(seen) == {(24, D, F) if scan else (8, D, F)}
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert takes_grouped(cfg, 40)


# ------------------------------------------------------ through the engine
def _served(monkeypatch, **changes):
    """(engine, what the expert layers sowed: one int32[2] a layer an
    execution, in order) of a small model served by an engine of three
    slots; the layer's `sow` is spied on through a host callback."""
    from flax.core import meta

    from ray_tpu.inference import EngineConfig, InferenceEngine
    from ray_tpu.models import TransformerLM
    sown = []
    sow = MoEMLP.sow

    def spy(self, col, name, value, **kw):
        if name == "rows_and_picks":
            jax.debug.callback(lambda v: sown.append(np.asarray(v)), value)
        return sow(self, col, name, value, **kw)

    monkeypatch.setattr(MoEMLP, "sow", spy)
    cfg = dataclasses.replace(_cfg(**changes), n_layers=2, max_seq_len=96,
                              remat=False)
    model = TransformerLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 8), jnp.int32))["params"])
    return InferenceEngine(model, params, EngineConfig(
        n_slots=3, max_len=96, prefill_chunk=16, prefill_budget=32)), sown


def _drain(eng, handles):
    while eng.sched.has_work():
        eng.step()
    jax.effects_barrier()
    return [list(h) for h in handles]


@pytest.mark.parametrize("scan", [True, False])
def test_the_engine_counts_the_rows_the_grouped_form_computed(monkeypatch,
                                                             scan):
    """Every expert held at C == L: `stats()` gives `moe_rows_computed`
    and `moe_local_picks`, the sums of what the layers sowed over steps
    with idle slots (two requests in three slots) and a padded tile (a
    prompt of 41 tokens in tiles of 32), and the tile programs' rows are
    whole row tiles under the dense form's."""
    eng, sown = _served(monkeypatch, scan_layers=scan)
    cfg = eng.model.cfg
    assert takes_grouped(cfg, 32 + 3) and takes_grouped(cfg, 16 + 3)
    prompts = [np.arange(41) % 60 + 1, np.arange(23) % 50 + 2]
    jax.effects_barrier()
    before, n = eng.stats(), len(sown)
    got = _drain(eng, [eng.submit(p, max_new_tokens=6) for p in prompts])
    assert [len(g) for g in got] == [6, 6]
    st = eng.stats()
    rows, picks = np.sum(sown[n:], axis=0)
    assert st["moe_rows_computed"] - before["moe_rows_computed"] == rows
    assert st["moe_local_picks"] - before["moe_local_picks"] == picks
    # every real row's K picks in both layers: the prompts' tokens and at
    # least five decode rows a request (the first token is the prompt's; a
    # row issued ahead of its request's end is computed too)
    assert picks % 4 == 0 and 41 + 23 + 2 * 5 <= picks // 4 <= 41 + 23 + 2 * 8
    # a decode step's layer: 8 experts x 3 one-row groups; a tile
    # program's: whole tiles of 16 (35 rows) or 8 (19 rows), each expert's
    # picks and less than a tile of padding, under the dense form's 8 x 35
    tiles = [(r, p) for r, p in sown[n:] if r != 8 * 3]
    assert tiles and all(r % 8 == 0 and p <= r <= p + 8 * 15 and r < 8 * 35
                         for r, p in tiles)
    assert picks < rows


def test_a_model_under_its_groups_capacity_reports_what_it_did(monkeypatch):
    """C < L with every expert held: the counts are the shapes', the
    engine reads none and `stats()` names none; with a share held, both,
    as before."""
    eng, _ = _served(monkeypatch, capacity_factor=1.5)
    _drain(eng, [eng.submit(np.arange(20) % 50 + 1, max_new_tokens=3)])
    assert "moe_rows_computed" not in eng.stats()
    eng, sown = _served(monkeypatch, capacity_factor=1.5,
                        experts_held=(2, 4))
    assert not takes_grouped(eng.model.cfg, 32 + 3)
    jax.effects_barrier()
    before, n = eng.stats(), len(sown)
    _drain(eng, [eng.submit(np.arange(20) % 50 + 1, max_new_tokens=3)])
    st = eng.stats()
    rows, picks = np.sum(sown[n:], axis=0)
    assert st["moe_rows_computed"] - before["moe_rows_computed"] == rows
    assert st["moe_local_picks"] - before["moe_local_picks"] == picks
    assert 0 < picks < rows
