"""A step's prefill tile and its decode rows as ONE program (the engine's
``prefill``; ``decode`` serves the steps that carry no prompt), on the CPU
in float32 for four models: dense, every expert held (capacity = the
group's length), a held share of narrow experts whose capacity overflows,
and the model with a sparse-attention indexer (whose engine keeps the two
programs a step: its decode row is not bound by the weights' stream).

- greedy tokens over a schedule that mixes prefill and decode equal the
  one-request generator's (``make_generate_fn``: one-shot prefill, then
  scalar-``idx`` decode, the two-program form);
- PR 28's rule: a prompt's K/V are the same bits whether its spans ran
  beside live slots, beside idle ones, or as a step's second span;
- the tile programs and the decode program compile when the engine is
  built / once, and never again; ``fused_steps`` and the prefill span's
  ``decode_rows`` count what they say; a prompt that ends in a step emits
  its second token in the next;
- the tile program's head runs over the rows it samples (the prompt's
  would-be next token and the riding rows, 1 + S of T + S) and no dot of
  it has a tile of vocabulary rows for a result; a cached call that names
  no rows gets every row's logits.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import events
from ray_tpu.inference import EngineConfig, InferenceEngine
from ray_tpu.models.generate import make_generate_fn
from ray_tpu.models.transformer import TransformerConfig, TransformerLM
from ray_tpu.parallel.mesh import MeshConfig, make_mesh

KINDS = ["dense", "moe", "moe-grouped", "moe-share-overflows", "indexer"]
_OVER = {
    "dense": {},
    "moe": dict(n_experts=4, expert_top_k=2, capacity_factor=2.0),
    # Mixtral's 8 experts, top-2, at C == L: the tile of 8 rows with the 4
    # slots' rows behind it takes the expert layer's grouped form
    # (models/moe.py `takes_grouped`), a decode step the dense dispatch
    "moe-grouped": dict(n_experts=8, expert_top_k=2, capacity_factor=4.0),
    # 8 narrow experts, 4 held, top-2; capacity ceil(0.5 * L / 4): half the
    # expected load, so picks overflow in every tile and most decode steps
    "moe-share-overflows": dict(n_experts=8, expert_top_k=2,
                                experts_held=(2, 4), capacity_factor=0.5),
    "indexer": dict(n_experts=8, expert_top_k=2, experts_held=(2, 4),
                    capacity_factor=2.0, qk_norm=True, index_heads=2,
                    index_head_dim=16, index_topk=6),
}


@functools.lru_cache(maxsize=None)
def model_of(kind):
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, **_OVER[kind])
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(len(kind)),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def engine_of(kind, **kw):
    model, params = model_of(kind)
    cfg = dict(n_slots=4, max_len=64, prefill_chunk=4, prefill_budget=8)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg))


def run_until(eng, cond, max_steps=400):
    for _ in range(max_steps):
        if cond():
            return
        eng.step()
    raise AssertionError("the engine did not get there")


def generated_alone(kind, prompt, n_new):
    """The request's greedy tokens from the one-program generator."""
    model, params = model_of(kind)
    mesh = make_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    _, gen, _ = make_generate_fn(model, mesh, batch=1,
                                 prompt_len=len(prompt),
                                 max_new_tokens=n_new)
    return np.asarray(gen(params, jnp.asarray(prompt)[None],
                          jax.random.PRNGKey(0)))[0].tolist()


# prompts of one span, of several, of a span that ends where the next
# request's begins; replies that outlast the prompts behind them
_SCHEDULE = [(5, 9), (19, 6), (3, 12), (11, 7), (26, 5), (8, 8)]


@pytest.mark.parametrize("kind", KINDS)
def test_mixed_schedule_gives_the_two_program_tokens(kind):
    eng = engine_of(kind, prefill_budget=16)
    assert eng.prefill_compile_count == len(eng._prefill_tiles) == 2
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 128, n) for n, _ in _SCHEDULE]
    handles = []
    for prompt, (_, n_new) in zip(prompts, _SCHEDULE):
        handles.append(eng.submit(prompt, max_new_tokens=n_new))
        eng.step()                  # arrivals land among running decodes
    run_until(eng, lambda: all(h.finish_reason for h in handles))
    for prompt, (_, n_new), h in zip(prompts, _SCHEDULE, handles):
        assert h.tokens() == generated_alone(kind, prompt, n_new)
    st = eng.stats()
    if kind == "indexer":
        assert st["fused_steps"] == 0       # its decode rows do not ride
    else:
        assert 0 < st["fused_steps"] < st["steps"]
    # no shape but the ones warmed when the engine was built
    assert eng.prefill_compile_count == len(eng._prefill_tiles)
    assert eng._prefill_fn._cache_size() == len(eng._prefill_tiles)
    assert eng.decode_compile_count == 1
    assert eng._decode_fn._cache_size() == 1


def _prompt_kv(kind, prompt, decoders, second):
    """Serve `prompt` on a fresh engine and return (its tokens, every
    pool's rows of its slot when its first token is out, the (offset,
    rows, decode rows) of its spans). `decoders`: two requests decode
    throughout. `second`: the first step's budget is two tiles and a
    three-token prompt ahead of it ends in that step's first program, so
    its first span (a whole tile: what the leftover of 13 holds) is that
    step's second program; otherwise the span is the step's first."""
    eng = engine_of(kind)
    rng = np.random.RandomState(11)
    others = []
    if decoders:
        others = [eng.submit(rng.randint(1, 128, n), max_new_tokens=40)
                  for n in (6, 2)]
        run_until(eng, lambda: all(h.first_token_t for h in others))
    spans, call = [], eng._call_prefill

    def spy(scratch, host):
        S = eng.config.n_slots      # the host's one array: _tile_args
        spans.append((int(host[S]), int(host[S + 1]), int(host[:S].sum())))
        return call(scratch, host)
    eng._call_prefill = spy
    if second:
        others.append(eng.submit(rng.randint(1, 128, 3), max_new_tokens=1))
        eng.sched.prefill_budget = 16
    h = eng.submit(prompt, max_new_tokens=5)
    eng.step()
    eng.sched.prefill_budget = 8
    run_until(eng, lambda: h.first_token_t is not None)
    slot = next(s for s, st in eng.sched._active.items() if st.handle is h)
    kv = [np.asarray(jnp.take(pool, slot, axis=1))
          for pool in eng._slots.pools()]
    run_until(eng, lambda: all(x.finish_reason for x in (h, *others)))
    own = spans[1:] if second else spans
    return h.tokens(), kv, own


@pytest.mark.parametrize("kind", KINDS)
def test_a_prompts_kv_are_the_same_bits_whatever_rides_behind(kind):
    prompt = np.random.RandomState(12).randint(1, 128, 21)
    alone, kv0, spans0 = _prompt_kv(kind, prompt, False, False)
    assert [s[:2] for s in spans0] == [(0, 8), (8, 8), (16, 5)]
    assert {s[2] for s in spans0} == {0}
    seen = set()
    for decoders, second in ((True, False), (False, True), (True, True)):
        toks, kv, spans = _prompt_kv(kind, prompt, decoders, second)
        assert [s[:2] for s in spans] == [s[:2] for s in spans0]
        seen.add(tuple(s[2] for s in spans))
        assert toks == alone
        for a, b in zip(kv, kv0):
            n = len(prompt)     # positions lie last in the indexer's keys
            a, b = ((a[..., :n], b[..., :n]) if a.shape[-1] == 64
                    else (a[:, :n], b[:, :n]))
            np.testing.assert_array_equal(a, b)
    # beside live rows from the first span on; a step's second program
    # carries none even then
    assert seen == ({(0, 0, 0)} if kind == "indexer" else
                    {(2, 2, 2), (0, 0, 0), (0, 2, 2)})


def tile_text(eng):
    """The lowered text of the program of `eng`'s largest tile."""
    tile = eng._prefill_tiles[-1]
    pools = eng._slots.pools() if eng._ride else ()
    return eng._prefill_fn.lower(
        eng.params, *eng._slots.new_scratch(), *pools, eng._carry,
        eng._tile_args(tile, np.zeros((tile,), np.int32), 0, 0, False, 0.0,
                       [])).as_text()


def tile_head_rows(eng):
    """The rows ([.., rows, vocab] -> rows) of every dot of a WEIGHT (a
    parameter of the program; a decode row's scores against a key block of
    64 positions x 2 KV heads are 128 wide too) in the lowered program of
    `eng`'s largest tile whose result ends in the vocabulary, and that
    tile's length."""
    dots = re.findall(
        r"dot_general %\w+, %arg\d+,.*-> tensor<([\dx]+)x\w+>",
        tile_text(eng))
    assert dots
    shapes = [tuple(map(int, d.split("x"))) for d in dots]
    vocab = eng.model.cfg.vocab_size
    return ([s[-2] for s in shapes if s[-1] == vocab],
            eng._prefill_tiles[-1])


@pytest.mark.parametrize("kind", KINDS)
def test_the_tile_program_unembeds_the_rows_it_samples(kind):
    eng = engine_of(kind)
    S = eng.config.n_slots
    rows, tile = tile_head_rows(eng)
    # one head, over the prompt's row and the riding rows (an indexer's
    # model has none behind its tile): never the tile's T (+ S)
    assert tile == 8 and rows == [1 if kind == "indexer" else 1 + S]


def test_a_cached_call_that_names_no_rows_gets_every_rows_logits():
    """A cached call that asks for no rows by name (`generate.py`'s, a
    teacher-forced reference's) gets every row's logits, and the rows a
    caller does name are those rows of it."""
    from ray_tpu.models.transformer import init_cache
    model, params = model_of("dense")
    toks = jnp.asarray(np.random.RandomState(3).randint(1, 128, (2, 9)))
    cache = init_cache(model.cfg, 2, 32, jnp.float32)
    whole, new = model.apply({"params": params}, toks, cache=cache)
    assert whole.shape == (2, 9, 128) and int(new["idx"]) == 9
    # the one-shot forward (no cache) is the same model over the same rows
    np.testing.assert_allclose(
        whole, model.apply({"params": params}, toks), atol=2e-5)
    rows = jnp.asarray([8, 0, 3], jnp.int32)
    named, again = model.apply({"params": params}, toks, cache=cache,
                               logit_rows=rows)
    assert named.shape == (2, 3, 128)
    np.testing.assert_allclose(named, whole[:, rows], atol=1e-6)
    for n in ("k", "v"):
        np.testing.assert_array_equal(again[n], new[n])
    with pytest.raises(ValueError, match="cached forward"):
        model.apply({"params": params}, toks, logit_rows=rows)


def test_counters_and_spans_count_what_they_say():
    events.drain()
    eng = engine_of("dense")
    a = eng.submit(np.arange(1, 4), max_new_tokens=6)
    eng.step()                      # a's prompt ends: no row was live
    assert eng.first_tokens == 1 and eng.sched._active
    assert eng.stats()["fused_steps"] == 0 and eng.tokens_generated == 0
    eng.step()                      # a decode-only step: the second token
    assert eng.tokens_generated == 1 and eng.stats()["fused_steps"] == 0
    b = eng.submit(np.arange(1, 14), max_new_tokens=3)     # spans 8 + 5
    eng.step()
    eng.step()
    st = eng.stats()
    assert st["fused_steps"] == 2 and st["steps"] == 4
    assert st["prefill_dispatches"] == 3 and eng.tokens_generated == 3
    # b's prompt ended in the fourth step: its first token is decided
    # (and goes out behind the next dispatch), its second comes with the
    # next step's decode rows, a's among them
    assert eng.first_tokens == 2 and b.first_token_t is None
    (state,) = [s for s in eng.sched.active_states() if s.handle is b]
    assert state.generated == 1
    eng.step()
    assert state.generated == 2 and eng.tokens_generated == 5
    assert eng.stats()["fused_steps"] == 2      # no span in that step
    run_until(eng, lambda: a.finish_reason and b.finish_reason)
    rows = [r["attrs"]["decode_rows"] for r in events.drain()
            if r.get("state") == "RUNNING" and r["name"] == "engine.prefill"]
    assert rows == [0, 1, 1]
