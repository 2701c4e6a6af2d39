"""The model with a learned sparse-attention indexer, q/k head norms, a
head size of its own and one rank's share of the experts, against its plain
reference (perfbench/families/keye_vl2_reference.py: the only copy), on the
CPU at a small size in float32: hidden 64, 4 heads / 2 KV heads of 32, 2
indexer heads of 16, top-16 selection, contexts to 96, 8 experts (top-2) of
which 2, 4 or all are held.

Three routes meet the reference on LOGITS (the one-shot forward, chunked
prefill then decode through caches laid out as the engine's pools, and
`make_generate_fn`), the selection meets the reference's set below, at and
above `topk`, the shares of a layer add up to the uncut layer, a request
that shares engine steps with others gives the same tokens as alone, each
planted fault is caught, and the two older families' trees and numbers are
where they were.
"""
import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from perfbench.families import (keye_vl2, keye_vl2_controls,
                                keye_vl2_reference as ref)
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models import MODEL_REGISTRY, TransformerLM
from ray_tpu.models import sparse_attention as sa
from ray_tpu.models.transformer import TransformerConfig, init_cache
from ray_tpu.ops import decode_attention

TOPK = 16


def config(held: int, rank: int = 1, n_experts: int = 8) -> dict:
    """The family's configuration file at the small size."""
    return {
        "hidden_size": 64, "head_dim": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "moe_intermediate_size": 48,
        "num_experts": n_experts, "num_local_experts": held,
        "num_experts_per_tok": 2, "num_hidden_layers": 2,
        "vocab_size": 257, "max_position_embeddings": 512,
        "rope_theta": 10000000, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "norm_topk_prob": True,
        "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 2,
                      "indexer_num_kv_heads": 1, "topk": TOPK},
        "deployment": {"expert_rank": rank if held < n_experts else 0},
        "param_dtype": "float32",
        "program": {"capacity_factor": 2.0},
    }


def build(m: dict, dtype="float32", **over):
    kw = keye_vl2.model_kwargs(m)
    kw.update(dtype=dtype, remat=False, **over)
    return keye_vl2.build_model(kw)


def seeded(model, seed=0):
    """Seeded float32 weights; the norms' scales and the indexer
    LayerNorm's bias are drawn too, so that each matters."""
    params = meta.unbox(model.init(jax.random.PRNGKey(seed),
                                   jnp.zeros((1, 8), jnp.int32))["params"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(treedef, [
        a + 0.2 * jax.random.normal(jax.random.PRNGKey(100 + i), a.shape)
        if path[-1].key in ("scale", "bias") else a
        for i, (path, a) in enumerate(leaves)])


def tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         1, 257))


HELD = pytest.mark.parametrize("held", [2, 4, 8])


@functools.lru_cache(maxsize=None)
def programs(model):
    """The model's forwards, jitted once a model: (whole sequence,
    a prefill tile against a cache, one row a slot against a cache)."""
    def cached(chunked):
        return jax.jit(lambda params, toks, cache: model.apply(
            {"params": params}, toks, cache=cache, chunked_prefill=chunked))
    return (jax.jit(lambda params, toks: model.apply({"params": params},
                                                     toks)),
            cached(True), cached(False))


@pytest.fixture(scope="module")
def small():
    """{held: (config dict, model, params, reference logits of 90 tokens)}"""
    out = {}
    for held in (2, 4, 8):
        m = config(held)
        model = build(m)
        params = seeded(model)
        out[held] = (m, model, params,
                     np.asarray(ref.logits(params, m, tokens(90))))
    return out


# ------------------------------------------------ three routes, on logits
@HELD
def test_one_shot_forward_matches_reference(small, held):
    """The uncached forward is the training forward, which drops a pick
    past its expert's capacity (Switch): compared at the capacity that
    holds every pick, experts / experts a token."""
    m, model, params, want = small[held]
    model = TransformerLM(dataclasses.replace(model.cfg, capacity_factor=4.0))
    got = programs(model)[0](params, jnp.asarray(tokens(90))[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def cached_logits(model, params, seqs, prompt_lens, tile, max_len):
    """Chunked prefill (tiles of `tile` rows into a one-row scratch, as
    the engine runs a prompt), the scratch made a slot of a pool of
    len(seqs) slots, then decode with every slot at its own length, one
    row a slot a step: the logits of every position of every sequence."""
    cfg = model.cfg
    _, prefill, decode = programs(model)
    names = [n for n in init_cache(cfg, 1, 8) if n != "idx"]
    pool = init_cache(cfg, len(seqs), max_len)
    out = [[] for _ in seqs]
    for b, (seq, n) in enumerate(zip(seqs, prompt_lens)):
        scratch = init_cache(cfg, 1, max_len + tile)
        for off in range(0, n, tile):
            chunk = np.zeros((1, tile), np.int32)
            real = min(tile, n - off)
            chunk[0, :real] = seq[off:off + real]
            lg, scratch = prefill(params, jnp.asarray(chunk), dict(
                scratch, idx=jnp.int32(off),
                real=jnp.arange(tile)[None, :] < real))
            scratch.pop("real", None)
            out[b].append(np.asarray(lg[0, :real]))
        for name in names:
            axis = 4 if name == "ki" else 2
            row = jax.lax.slice_in_dim(scratch[name], 0, max_len, axis=axis)
            pool[name] = jax.lax.dynamic_update_slice(
                pool[name], row, (0, b, 0, 0, 0))
    lens = np.asarray(prompt_lens, np.int32)
    for step in range(max(len(s) - n for s, n in zip(seqs, prompt_lens))):
        toks = np.asarray([s[min(n + step, len(s) - 1)]
                           for s, n in zip(seqs, prompt_lens)], np.int32)
        lg, new = decode(params, jnp.asarray(toks)[:, None],
                         dict(pool, idx=jnp.asarray(lens + step)))
        pool = {k: v for k, v in new.items() if k != "idx"}
        for b, (s, n) in enumerate(zip(seqs, prompt_lens)):
            if n + step < len(s):
                out[b].append(np.asarray(lg[b]))
    return [np.concatenate(o) for o in out]


@HELD
def test_prefill_then_decode_through_pools_matches_reference(small, held):
    """Two slots at their own lengths (one below `topk` at its first
    decode row, one far above it): what chunked prefill and then decoding
    through the three pools produce is the reference's full forward."""
    m, model, params, want = small[held]
    a, b = tokens(90), tokens(30, seed=2)
    got = cached_logits(model, params, [a, b], [61, 9], tile=16, max_len=96)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    np.testing.assert_allclose(
        got[1], np.asarray(ref.logits(params, m, b)), atol=2e-5)


@HELD
def test_make_generate_fn_matches_reference(small, held):
    """The one-program generator (one-shot prefill into the caches, then a
    scan of decode steps): every token it chose is the reference's argmax
    at its position, teacher-forced."""
    from ray_tpu.models.generate import make_generate_fn
    from ray_tpu.parallel import MeshConfig, make_mesh
    m, model, params, _ = small[held]
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, seq=1, tensor=1),
                     devices=jax.devices()[:1])
    _, generate, _ = make_generate_fn(model, mesh, batch=2, prompt_len=40,
                                      max_new_tokens=12)
    prompts = np.stack([tokens(40, seed=3), tokens(40, seed=4)])
    out = np.asarray(generate(params, jnp.asarray(prompts),
                              jax.random.PRNGKey(0)))
    for p, g in zip(prompts, out):
        gaps = ref.teacher_forced_gaps(params, m, p.tolist(), g.tolist())
        assert max(gaps) <= 1e-5, gaps


# ------------------------------------------------------------- selection
def _indexer_inputs(L, ties=False, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    qi = jax.random.normal(ks[0], (1, L, 2, 16))
    ki = jax.random.normal(ks[1], (1, 1, 16, L))
    w = jax.random.normal(ks[2], (1, L, 2))
    if ties:          # few distinct scores: many ties at the topk-th
        qi, ki, w = jnp.round(qi), jnp.round(ki), jnp.round(w)
    return qi, ki, w


def _reference_set(qi, ki, w, topk=TOPK):
    L = qi.shape[1]
    per_head = jax.nn.relu(jnp.einsum("qjk,kl->qjl", qi[0], ki[0, 0]))
    scores = jnp.einsum("qj,qjl->ql", w[0], per_head)
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    return np.asarray(ref.selected(jnp.where(causal, scores, -jnp.inf),
                                   topk))


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("L", [8, 16, 17, 96])
def test_selection_is_the_references_set(L, ties):
    """Below, at, one past and far above `topk`; with ties at the topk-th
    score, which go to the lower position in both."""
    qi, ki, w = _indexer_inputs(L, ties)
    want = _reference_set(qi, ki, w)
    qpos = jnp.arange(L)[None, :]
    got = np.asarray(sa.select(sa.index_scores(qi, w, ki, qpos), TOPK))[0]
    assert (got == want).all()
    assert (want.sum(-1) == np.minimum(np.arange(L) + 1, TOPK)).all()


@pytest.mark.parametrize("L", [8, 16, 17, 96])
def test_decode_attends_the_references_set(L):
    """The last row's attention over the caches, in place under the mask
    it selected, equals masked attention over the reference's set."""
    qi, ki, w = _indexer_inputs(L)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (1, L, 4, 32))
    k = jax.random.normal(ks[1], (1, L, 2, 32))
    v = jax.random.normal(ks[2], (1, L, 2, 32))
    mask = jnp.asarray(_reference_set(qi, ki, w))[None]
    want = sa.masked_attention(q, k, v, mask)[:, -1]
    pad = 128 - (L - 1)           # the caches hold the rows before the last
    got = sa.sparse_decode_attention(
        q[:, -1:], k[:, -1:], v[:, -1:], qi[:, -1:], w[:, -1:],
        ki[..., -1:], jnp.pad(k[:, :-1], ((0, 0), (0, pad), (0, 0), (0, 0))),
        jnp.pad(v[:, :-1], ((0, 0), (0, pad), (0, 0), (0, 0))),
        jnp.pad(ki[..., :-1], ((0, 0), (0, 0), (0, 0), (0, pad))),
        jnp.int32(L - 1), TOPK)[:, 0]
    np.testing.assert_allclose(got, want, atol=1e-5)


# slots' lengths (the decode row is a sequence's last; its cache holds the
# rows before it) and `topk`: below, at, one past and far above `topk`;
# slots of different lengths in one call, one of them idle at length 0;
# and a `topk` past M + 1, which is every live position
DECODE_CASES = {
    "below": ([8], TOPK), "at": ([16], TOPK), "one_past": ([17], TOPK),
    "far_above": ([96], TOPK), "mixed": ([96, 8, 1, 17, 40], TOPK),
    "every_live": ([96, 17], 10 ** 6)}
CACHE_LEN = 128


def _decode_case(Ls, ties, topk, seed=11):
    """Slots whose sequences have lengths `Ls`: the arguments of
    `sparse_decode_attention` in the [B, M, ..] form (the caches' dead
    rows hold finite noise) and what masked attention over the
    reference's set gives the last row of each."""
    rows, want = [], []
    for b, L in enumerate(Ls):
        qi, ki, w = _indexer_inputs(L, ties, seed=seed + b)
        ks = jax.random.split(jax.random.PRNGKey(seed + 100 + b), 4)
        q = jax.random.normal(ks[0], (1, L, 4, 32))
        k = jax.random.normal(ks[1], (1, L, 2, 32))
        v = jax.random.normal(ks[2], (1, L, 2, 32))
        mask = jnp.asarray(_reference_set(qi, ki, w, topk))[None]
        want.append(sa.masked_attention(q, k, v, mask)[:, -1])
        pad = CACHE_LEN - (L - 1)
        noise = jax.random.normal(ks[3], (3, 1, pad, 2, 32))
        rows.append((
            q[:, -1:], k[:, -1:], v[:, -1:], qi[:, -1:], w[:, -1:],
            ki[..., -1:], jnp.concatenate([k[:, :-1], noise[0]], 1),
            jnp.concatenate([v[:, :-1], noise[1]], 1),
            jnp.concatenate([ki[..., :-1], noise[2, :, :, 0, :16].transpose(
                0, 2, 1)[:, None]], -1)))
    args = [jnp.concatenate(a) for a in zip(*rows)]
    return args, jnp.asarray([L - 1 for L in Ls], jnp.int32), \
        jnp.concatenate(want)


@pytest.fixture
def kernel_interpreted(monkeypatch):
    """`sparse_decode_attention` takes the Pallas kernel, interpreted."""
    monkeypatch.setattr(sa, "_kernel_reads", lambda *shape: True)
    monkeypatch.setattr(
        decode_attention, "pool_decode_attention", functools.partial(
            decode_attention.pool_decode_attention, interpret=True))


@pytest.mark.parametrize("form", ["rows", "pool", "kernel"])
@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_decode_row_attends_the_references_set_in_place(case, ties, form,
                                                        request):
    """Every slot's row equals masked attention over the reference's set:
    in the [B, M, ..] form, in the pool-with-`layer` form at a layer other
    than 0 (the other layers hold noise), and through the kernel,
    interpreted, in place of the XLA loop; with ties at the topk-th score
    the row's own key, the highest position, loses them."""
    Ls, topk = DECODE_CASES[case]
    args, lens, want = _decode_case(Ls, ties, topk)
    layer = ()
    if form != "rows":
        if form == "kernel":
            request.getfixturevalue("kernel_interpreted")
        noise = jax.random.PRNGKey(3)
        args[6:] = [jnp.stack([jax.random.normal(noise, a.shape), a,
                               -jax.random.normal(noise, a.shape)])
                    for a in args[6:]]
        layer = (jnp.int32(1),)
    got = jax.jit(lambda *a: sa.sparse_decode_attention(
        *a[:9], a[9], topk, *a[10:]))(*args, lens, *layer)[:, 0]
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("lens", [[0, 5, 15, 16], [17, 40, 3, 31],
                                  [90, 127, 64, 0]])
def test_decode_rows_mask_is_the_stable_sorts_set(lens, ties):
    """The mask over a slot's positions and the row's own equals the first
    `topk` of a stable sort by falling score (the form the decode row had:
    kept here as the oracle), slots of several lengths in one call, the
    dead positions at -inf. The search has one digit width whatever the
    shape (2 bits: a wider one gained nothing for a few rows, PERF.md
    section 6, PR 41)."""
    M = CACHE_LEN
    lens = jnp.asarray(lens)
    scores = jax.random.normal(jax.random.PRNGKey(int(lens[0])), (4, M + 1))
    # few distinct scores: many ties at the topk-th (0.0, not -0.0: the
    # decode row hands the search no negative zero)
    scores = jnp.round(scores) if ties else scores
    scores = jnp.where(scores == 0, 0.0, scores)
    scores = jnp.where(jnp.arange(M + 1)[None] < lens[:, None], scores,
                       -jnp.inf).at[:, M].set(scores[:, M])
    idx = jnp.argsort(-scores, axis=-1, stable=True)[:, :TOPK]
    want = np.zeros((4, M + 1), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    want &= np.asarray(scores) > -np.inf
    got = sa.select(scores, TOPK)
    assert (np.asarray(got) == want).all()
    assert (want.sum(-1) == np.minimum(np.asarray(lens) + 1, TOPK)).all()


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("lens", [[0, 0, 0], [1, 33, 128], [64, 0, 97]])
def test_pool_kernel_interpreted_is_the_xla_loop(lens, masked):
    """ops/decode_attention.py: the kernel (interpreted) and the XLA loop
    give the same running softmax of a pool's layer, slots at their own
    lengths (an idle one at 0), with a mask and without, over blocks
    smaller than the cache so that a slot's dead blocks are met."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (3, 4, 32))
    kp = jax.random.normal(ks[1], (3, 3, CACHE_LEN, 2, 32))
    vp = jax.random.normal(ks[2], (3, 3, CACHE_LEN, 2, 32))
    mask = jax.random.bernoulli(ks[3], 0.4, (3, CACHE_LEN)) if masked \
        else None
    args = (q, kp, vp, jnp.int32(2), jnp.asarray(lens, jnp.int32), mask)
    want = decode_attention.pool_decode_reference(*args, max_block=32)
    got = decode_attention.pool_decode_attention(*args, max_block=32,
                                                 interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-5)
    if not masked and max(lens) == 0:
        assert float(jnp.abs(got[2]).max()) == 0.0   # nothing attended


def _tile_program():
    model = build(config(4))
    S = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    cache = dict(jax.eval_shape(lambda: init_cache(model.cfg, 1, 112)),
                 idx=S((), jnp.int32), real=S((1, 16), jnp.bool_))
    return jax.jit(lambda p, toks, c: model.apply(
        {"params": p}, toks, cache=c, chunked_prefill=True)).lower(
        params, S((1, 16), jnp.int32), cache)


def _block_select_program(B, S):
    # MiniCPM-SALA's geometry against a 17,408-position cache's kernels
    geo = sa.BlockGeometry(64, 32, 16, 1, 2048, 64)
    s = jax.ShapeDtypeStruct
    return jax.jit(lambda q, kp, qpos: sa.block_select(
        q, kp, qpos, geo)).lower(
        s((B, S, 4, 32), jnp.float32), s((B, 1088, 2, 32), jnp.float32),
        s((B, S), jnp.int32))


@pytest.mark.parametrize("program,digest", [
    (_tile_program, "eba88afd506ec9e5"),
    (functools.partial(_block_select_program, 1, 8), "9077eaa2cb26a582"),
    (functools.partial(_block_select_program, 4, 1), "b075abdd0ab7d65c")])
def test_programs_the_decode_row_shares_code_with_are_the_parents(program,
                                                                  digest):
    """The lowered text of the indexer model's TILE program and of the
    selection by block (a tile's and the decode rows') as read on the
    commit before the decode row's selection took a digit of its own
    (PR 40's tree): the radix search and the blocked softmax they share
    with it reach their old defaults, line for line."""
    text = program().as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# ------------------------------------------------------ shares of a layer
@pytest.mark.parametrize("held", [1, 2, 4])
def test_shares_of_a_layer_add_up_to_the_uncut_layer(held):
    """Every rank's share of one layer, in the PROGRAM, with what all
    ranks compute alike (attention, the residual) counted once, adds up
    to the uncut REFERENCE layer."""
    whole = config(8)
    x = jax.random.normal(jax.random.PRNGKey(7), (40, 64))
    model = build(whole)
    params = seeded(model)
    layer0 = jax.tree.map(lambda a: a[0], params["layers"]["block"])
    want = np.asarray(ref.layer(x, layer0, whole))
    from ray_tpu.models.transformer import Block
    total, alike = 0.0, None
    for rank in range(8 // held):
        share = build(config(held, rank)).cfg
        first = rank * held
        p = dict(layer0, moe={
            "router": layer0["moe"]["router"],
            **{n: layer0["moe"][n][first:first + held]
               for n in ("gate", "up", "down")}})
        out, _ = Block(share).apply({"params": p}, x[None],
                                    jnp.arange(40)[None])
        empty = dict(p, moe=dict(p["moe"], down=jnp.zeros_like(
            p["moe"]["down"])))
        alike, _ = Block(share).apply({"params": empty}, x[None],
                                      jnp.arange(40)[None])
        total = total + (out - alike)
    np.testing.assert_allclose((alike + total)[0], want, atol=2e-5)


def test_serving_drops_nothing_and_training_drops():
    """A capacity of ONE row an expert: nearly every pick overflows. The
    serving forward (through a cache) still gives the reference's result,
    by the overflow route; the training forward (no cache) drops, as
    Switch does. Which of the two runs follows from the call, no option."""
    m = config(4)
    m["program"]["capacity_factor"] = 0.01
    model = build(m)
    params = seeded(model)
    want = np.asarray(ref.logits(params, m, tokens(40)))
    (got,) = cached_logits(model, params, [tokens(40)], [40], tile=16,
                           max_len=48)
    np.testing.assert_allclose(got, want, atol=2e-5)
    dropped = programs(model)[0](params, jnp.asarray(tokens(40))[None])[0]
    assert np.abs(np.asarray(dropped) - want).max() > 1e-3


def test_padded_rows_take_no_capacity_and_are_not_counted():
    """A tile whose padded tail is one id: unmasked, its rows all pick the
    same experts, ahead of no real row but counted; with `real` they are
    routed nowhere: the counts are the real rows' alone, whatever the tail
    holds, and the real rows' result does not move."""
    from ray_tpu.models.moe import MoEMLP
    cfg = build(config(4)).cfg
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 64))
    layer = MoEMLP(cfg)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    real = jnp.arange(32)[None, :] < 20

    def run(x, real):
        (out, _), counted = layer.apply({"params": params}, x, real,
                                        exact=True, mutable=["counters"])
        (pair,) = jax.tree.leaves(counted)
        return np.asarray(out), np.asarray(pair)

    out_a, pair_a = run(x, real)
    out_b, pair_b = run(x.at[:, 20:].set(x[:, 0:1]), real)
    _, pair_all = run(x, None)
    np.testing.assert_allclose(out_a[:, :20], out_b[:, :20], atol=1e-6)
    assert (pair_a == pair_b).all() and pair_a[1] < pair_all[1]
    assert np.abs(out_a[:, 20:]).max() == 0.0


# ------------------------------------------------------------ the engine
@pytest.fixture(scope="module")
def engine(small):
    _, model, params, _ = small[4]
    return InferenceEngine(model, params, EngineConfig(
        n_slots=3, max_len=96, prefill_chunk=16, prefill_budget=32))


def _drain(eng, handles):
    while eng.sched.has_work():
        eng.step()
    return [list(h) for h in handles]


def test_engine_tokens_are_the_references_and_counters_count(small, engine):
    m, _, params, _ = small[4]
    prompt = tokens(70, seed=8)
    before = engine.stats()
    (got,) = _drain(engine, [engine.submit(prompt, max_new_tokens=10)])
    gaps = ref.teacher_forced_gaps(params, m, prompt.tolist(), got)
    assert max(gaps) <= 1e-5, gaps
    st = engine.stats()
    live = sum(range(71, 80))       # nine decode rows, at lengths 71..79
    assert st["dsa_rows_live"] - before["dsa_rows_live"] == live
    assert st["dsa_rows_read"] - before["dsa_rows_read"] == 9 * TOPK
    # what the decode attention passes over: the cache's 70..78 positions
    # in whole key blocks (32 divides the slot's 96), and the row's own
    block = decode_attention.block_of(96)
    assert block == 32
    assert st["dsa_rows_streamed"] - before["dsa_rows_streamed"] == sum(
        -(-n // block) * block + 1 for n in range(70, 79))
    # rows of several lengths: each its own blocks where the kernel
    # reads, the longest one's for every row where the XLA loop does
    assert sa.decode_positions_read([5, 40, 70], 96, 2, 32) == 3 * 97
    assert sa.decode_positions_read([0], 96, 2, 32) == 1
    picks = st["moe_local_picks"] - before["moe_local_picks"]
    rows = st["moe_rows_computed"] - before["moe_rows_computed"]
    assert 0 < picks <= rows
    assert st["kv_pool_bytes"] == 2 * 3 * 96 * (2 * 2 * 32 + 16) * 4
    assert engine.decode_compile_count == 1


def test_counters_count_where_the_layers_are_not_scanned():
    """One pair of counts a layer, summed into the one output the engine
    folds: the unscanned layout counts what the scanned one does."""
    got = []
    for scan in (True, False):
        model = build(config(4), scan_layers=scan)
        params = meta.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
        if not scan:      # the same weights, a layer at a time
            params = dict(got[0][1], **{
                f"layer_{i}": jax.tree.map(lambda a: a[i],
                                           got[0][1]["layers"]["block"])
                for i in range(2)})
            del params["layers"]
        eng = InferenceEngine(model, params, EngineConfig(
            n_slots=2, max_len=96, prefill_chunk=16, prefill_budget=32))
        toks = _drain(eng, [eng.submit(tokens(40, seed=8),
                                       max_new_tokens=6)])
        st = eng.stats()
        got.append(((toks, st["moe_rows_computed"], st["moe_local_picks"]),
                    params))
    assert got[0][0] == got[1][0] and got[0][0][2] > 0


def test_a_request_among_others_gives_the_tokens_it_gives_alone(engine):
    """One tile a prompt: what shares a request's engine steps changes
    neither its selection nor its experts' result."""
    prompts = [tokens(n, seed=20 + n) for n in (70, 23, 55, 40)]
    alone = [_drain(engine, [engine.submit(p, max_new_tokens=8)])[0]
             for p in prompts]
    together = _drain(engine, [engine.submit(p, max_new_tokens=8)
                               for p in prompts])
    assert together == alone


def test_engine_refuses_what_does_not_carry_the_third_cache(small):
    _, model, params, _ = small[4]
    with pytest.raises(ValueError, match="indexer"):
        InferenceEngine(model, params, EngineConfig(
            n_slots=2, max_len=96, prefill_chunk=16, prefill_budget=16,
            prefix_cache_slots=1))
    with pytest.raises(keye_vl2.SpecError, match="prefix"):
        keye_vl2.model_kwargs(dict(config(4), engine={
            "max_len": 96, "prefix_cache_slots": 2}))


# ------------------------------------------------------ planted faults
# The comparison a serving cell makes: the share of generated tokens whose
# reference logit lies within `logit_gap` of their position's largest.
# Here in float32 the sound program leaves every token at a gap of 0, so
# each fault has only to move one.
def _prefilled(model, params, prompt):
    """(the cache after chunked prefill of `prompt`, its last logits)."""
    _, prefill, _ = programs(model)
    cache = init_cache(model.cfg, 1, 96 + 16)
    last = None
    for off in range(0, len(prompt), 16):
        chunk = np.zeros((1, 16), np.int32)
        real = min(16, len(prompt) - off)
        chunk[0, :real] = prompt[off:off + real]
        lg, cache = prefill(params, jnp.asarray(chunk),
                            dict(cache, idx=jnp.int32(off)))
        last = lg[0, real - 1]
    return dict(cache, idx=jnp.int32(len(prompt))), last


def _served(model, params, prompt, n_new, cache_fault=None):
    """Greedy tokens through chunked prefill and decode (one slot)."""
    cache, last = _prefilled(model, params, prompt)
    out = [int(jnp.argmax(last))]
    if cache_fault is not None:
        cache = cache_fault(cache)
    for _ in range(n_new - 1):
        lg, cache = programs(model)[2](params, jnp.asarray([[out[-1]]]),
                                       cache)
        out.append(int(jnp.argmax(lg[0, -1])))
    return out


FAULTS = ["attends_every_live_position", "indexer_keys_shifted_by_one",
          "indexer_keys_of_another_slot", "an_expert_dropped",
          "matmuls_below_bf16"]


@pytest.mark.parametrize("fault", [None] + FAULTS)
def test_planted_fault_is_caught(small, fault):
    """Each fault moves the served tokens off the reference's choices, at a
    context past `topk`; the sound program, served the same way, does not.
    Greedy decoding diverges after a first differing token, so the count
    that is compared is of tokens that are not the reference's argmax."""
    m, model, params, _ = small[4]
    prompt = tokens(70, seed=9).tolist()
    served, cache_fault = params, None
    if fault == "attends_every_live_position":
        model = TransformerLM(dataclasses.replace(model.cfg,
                                                  index_topk=10 ** 6))
    elif fault == "indexer_keys_shifted_by_one":
        def cache_fault(c):
            return dict(c, ki=jnp.roll(c["ki"], 1, axis=4))
    elif fault == "indexer_keys_of_another_slot":
        other = _prefilled(model, params, tokens(70, seed=10))[0]["ki"]

        def cache_fault(c):
            return dict(c, ki=other)
    elif fault == "an_expert_dropped":
        moe = params["layers"]["block"]["moe"]
        served = dict(params, layers={"block": dict(
            params["layers"]["block"], moe=dict(
                moe, down=moe["down"].at[:, 1].set(0.0)))})
    elif fault == "matmuls_below_bf16":
        def four_bits(a):      # a float8's mantissa (e4m3: 3 bits + 1)
            mant, exp = np.frexp(np.asarray(a))
            return jnp.asarray(np.ldexp(np.round(mant * 16.0) / 16.0, exp))
        served = jax.tree.map(
            lambda a: four_bits(a) if a.ndim > 2 else a, params)
    got = _served(model, served, prompt, 12, cache_fault)
    gaps = ref.teacher_forced_gaps(params, m, prompt, got)
    wrong = sum(g > 1e-5 for g in gaps)
    assert (wrong == 0) if fault is None else (wrong > 0), (fault, gaps)


@pytest.mark.parametrize("control", list(keye_vl2_controls.CONTROLS))
def test_controls_of_the_cell_tell_each_fault_from_the_sound_program(control):
    """The tool that plants the same faults at the published widths on the
    chip (perfbench/families/keye_vl2_controls.py), here at the small size
    through the engine: the sound program passes the cell's comparison and
    each control fails it."""
    from perfbench import spec
    cfg = dict(
        config(4), family="keye_vl2", _family_file=keye_vl2.__file__,
        engine={"n_slots": 3, "max_len": 192, "prefill_chunk": 16,
                "prefill_budget": 32, "prefix_cache_slots": 0},
        reference_tolerance={"logit_gap": 1e-5, "share_within": 1.0})
    cfg["program"] = dict(cfg["program"], dtype="float32", remat=False)
    mix = {"reference_cases": [[12, 6], [70, 12], [90, 12]]}
    assert spec.family_of(cfg).teacher_forced_gaps
    (row,) = keye_vl2_controls.readings(cfg, mix, 5, [control])
    assert row["n_tokens"] == 30 and len(row["gaps"]) == 3
    assert row["passes"] == (control == "sound"), (
        control, row["beyond"], row["max_gap"])
    assert sa.index_scores.__name__ == "index_scores"     # put back


# ---------------------------------------------- the two older families
@pytest.mark.parametrize("name,digest,total,probe", [
    ("llama-debug", "b861695ad32055c1", 12615.443359375,
     0.015698928385972977),
    ("moe-debug", "abcf174cfbd7a4ae", 12426.5380859375,
     0.06323748826980591)])
def test_older_families_trees_and_numbers_unchanged(name, digest, total,
                                                    probe):
    """The parameter tree (paths, shapes, dtypes) and the forward's numbers
    of the dense and the all-experts-held models, as read on the commit
    before this model came (PR 32's tree): a head size, norms, an indexer
    or a share that leaked into them would move one."""
    cfg = dataclasses.replace(MODEL_REGISTRY[name], dtype=jnp.float32,
                              param_dtype=jnp.float32)
    assert cfg.head_dim == cfg.d_model // cfg.n_heads
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              cfg.vocab_size)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), toks)["params"])
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    spec = sorted(("/".join(k.key for k in p), tuple(a.shape), str(a.dtype))
                  for p, a in leaves)
    assert hashlib.sha256(repr(spec).encode()).hexdigest()[:16] == digest
    lg = model.apply({"params": params}, toks)
    np.testing.assert_allclose(float(jnp.abs(lg).sum()), total, rtol=1e-6)
    np.testing.assert_allclose(float(lg[1, 7, 3]), probe, rtol=1e-5)


def test_config_states_its_head_size():
    assert TransformerConfig(d_model=64, n_heads=4).head_dim == 16
    assert TransformerConfig(d_model=64, n_heads=4, head_dim=32).head_dim \
        == 32
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert {"head_dim", "qk_norm", "index_heads", "index_head_dim",
            "index_topk", "experts_held"} <= fields


# ------------------------------------------- the family, as the harness uses it
def test_family_seeds_every_leaf_and_counts_the_new_mathematics():
    """`perfbench/weights.py` draws the model's whole tree by the family's
    rule (a leaf without one raises), and the counts the roofline readers
    divide by follow the selection: a decode row reads min(live, topk) rows
    of K and V and the live rows of indexer keys, the weights stored are
    the held experts'."""
    from perfbench import weights
    m = dict(config(4), engine={"n_slots": 3, "max_len": 96})
    model = build(m)
    params = weights.seeded_params(model, 7, keye_vl2.weight_rule)
    attn = params["layers"]["block"]["attn"]
    assert float(jnp.abs(attn["index_k_norm"]["bias"]).max()) == 0.0
    assert float(attn["k_norm"]["scale"].min()) == 1.0
    assert 1.0 < float(jnp.std(attn["q_norm"]["scale"])) < 3.0
    assert params["layers"]["block"]["moe"]["gate"].shape == (2, 4, 64, 48)
    with pytest.raises(KeyError):
        keye_vl2.weight_rule(["layers", "block", "attn", "nope"], (2, 3))
    stored = keye_vl2.stored_param_bytes(m, 2.0)
    n_tree = sum(a.size for a in jax.tree.leaves(params) if a.ndim > 2) \
        + 2 * 257 * 64
    assert stored == 2.0 * n_tree
    kv_row, ki_row = 2 * 2 * 32 * 2.0, 16 * 2.0
    base = keye_vl2.decode_step_bytes(m, [], 2.0, 2.0)
    assert base == stored - 257 * 64 * 2.0
    assert keye_vl2.decode_step_bytes(m, [10, 90], 2.0, 2.0) - base == \
        2 * ((10 + TOPK) * kv_row + 100 * ki_row)
    assert keye_vl2.train_step_flops(m, 2, 64) > \
        keye_vl2.causal_attention_flops(m, 2, 64, backward=True) > 0
