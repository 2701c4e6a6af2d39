"""Continuous-batching inference engine (ray_tpu/inference/): slot-pool
admission/eviction semantics, chunked-prefill correctness, greedy parity
with make_generate_fn, and the one-compile decode contract.

CPU-pinned and cluster-free: the engine is pure JAX + host threading, so
every test here runs in tier-1 (JAX_PLATFORMS=cpu, any Python)."""

import re
import time

import numpy as np
import pytest


@pytest.fixture(scope="module")
def jax_cpu():
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    return jax


def _tiny_model(jax, seed=0, **kw):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, TransformerLM
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, **kw)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    return _tiny_model(jax_cpu)


def _engine(model, params, **kw):
    from ray_tpu.inference import EngineConfig, InferenceEngine
    cfg = dict(n_slots=2, max_len=48, prefill_chunk=4, prefill_budget=8)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg))


def _run_until(eng, cond, max_steps=300):
    for _ in range(max_steps):
        eng.step()
        if cond():
            return True
    return False


def test_mid_decode_admission(tiny):
    """A request submitted while another decodes starts (first token
    emitted) BEFORE the first finishes — the continuous-batching
    property the fixed-batch path lacks."""
    _, model, params = tiny
    eng = _engine(model, params)
    rng = np.random.RandomState(0)
    a = eng.submit(rng.randint(0, 128, 10), max_new_tokens=20)
    assert _run_until(eng, lambda: a.first_token_t is not None, 20)
    assert a.finish_reason is None
    b = eng.submit(rng.randint(0, 128, 3), max_new_tokens=4)
    assert _run_until(eng, lambda: b.first_token_t is not None, 20)
    # B started while A was still mid-decode
    assert a.finish_reason is None
    assert _run_until(eng, lambda: a.finish_reason and b.finish_reason)
    assert len(a.tokens()) == 20 and len(b.tokens()) == 4


def test_chunked_prefill_matches_one_shot_through_engine(tiny):
    """The same prompt admitted through 4-token prefill chunks and
    through one whole-prompt chunk yields identical greedy tokens."""
    _, model, params = tiny
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 128, 11)
    outs = []
    for chunk in (4, 16):     # 11 tokens: 3 chunks vs one chunk
        eng = _engine(model, params, prefill_chunk=chunk,
                      prefill_budget=16)
        h = eng.submit(prompt, max_new_tokens=8)
        assert _run_until(eng, lambda: h.finish_reason is not None)
        outs.append(h.tokens())
    assert outs[0] == outs[1]


def test_eviction_reuses_slots(tiny):
    """EOS, max-tokens and cancellation all free the slot for the next
    queued request; a single-slot engine serves a stream of requests."""
    _, model, params = tiny
    eng = _engine(model, params, n_slots=1)
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, 128, 5)

    # max-tokens eviction
    h1 = eng.submit(prompt, max_new_tokens=6)
    assert _run_until(eng, lambda: h1.finish_reason is not None)
    assert h1.finish_reason == "length" and len(h1.tokens()) == 6
    assert eng.stats()["slots_free"] == 1

    # EOS eviction: re-run greedily with eos set to the 3rd token
    h2 = eng.submit(prompt, max_new_tokens=6)
    assert _run_until(eng, lambda: h2.finish_reason is not None)
    third = h2.tokens()[2]
    h3 = eng.submit(prompt, max_new_tokens=6, eos_id=int(third))
    assert _run_until(eng, lambda: h3.finish_reason is not None)
    assert h3.finish_reason == "eos" and len(h3.tokens()) == 3
    assert eng.stats()["slots_free"] == 1

    # cancellation eviction frees the slot for a queued request
    h4 = eng.submit(prompt, max_new_tokens=500)
    h5 = eng.submit(prompt, max_new_tokens=4)     # queued behind h4
    assert _run_until(eng, lambda: h4.first_token_t is not None, 20)
    assert eng.stats()["queue_depth"] == 1
    h4.cancel()
    assert _run_until(eng, lambda: h5.finish_reason is not None)
    assert h4.finish_reason == "cancelled"
    assert len(h5.tokens()) == 4
    assert eng.stats()["slots_free"] == 1 and eng.stats()["queue_depth"] == 0

    # slot-capacity eviction (prompt 5 + 43 decodes fills max_len 48)
    h6 = eng.submit(prompt, max_new_tokens=10_000)
    assert _run_until(eng, lambda: h6.finish_reason is not None)
    assert h6.finish_reason == "length"
    assert len(h6.tokens()) == 48 - len(prompt) + 1


def test_greedy_matches_make_generate_fn(tiny):
    """Greedy tokens through the engine (chunked prefill + slot-pool
    decode + shared sampling) match the one-program generator
    token-for-token."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import make_generate_fn
    from ray_tpu.parallel import MeshConfig, make_mesh
    _, model, params = tiny
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, seq=1, tensor=1),
                     devices=jax.devices()[:1])
    B, P, N = 2, 10, 8
    rng = np.random.RandomState(3)
    prompts = rng.randint(0, 128, size=(B, P)).astype(np.int32)
    _, gen_fn, _ = make_generate_fn(model, mesh, batch=B, prompt_len=P,
                                    max_new_tokens=N)
    want = np.asarray(gen_fn(params, jnp.asarray(prompts),
                             jax.random.PRNGKey(7)))
    eng = _engine(model, params)
    hs = [eng.submit(prompts[i], max_new_tokens=N) for i in range(B)]
    assert _run_until(eng, lambda: all(h.finish_reason for h in hs))
    got = np.stack([h.tokens() for h in hs])
    np.testing.assert_array_equal(got, want)


def test_decode_compiles_exactly_once(tiny):
    """Across admissions, evictions, cancellations and slot reuse the
    decode step never retraces: one XLA program for the engine's life
    (the donated fixed-shape slot pool is the point of the design)."""
    _, model, params = tiny
    eng = _engine(model, params)
    rng = np.random.RandomState(4)
    # staggered mixed-length workload exercising every transition
    hs = []
    for i in range(6):
        hs.append(eng.submit(rng.randint(0, 128, 3 + 5 * (i % 3)),
                             max_new_tokens=3 + 4 * (i % 2)))
        eng.step()
        eng.step()
    hs[3].cancel()
    assert _run_until(eng, lambda: all(h.finish_reason for h in hs))
    assert eng.decode_compile_count == 1
    # every prefill tile compiled when the engine was built, none since
    assert eng.prefill_compile_count == len(eng._prefill_tiles) == 1
    # the jit caches agree with the trace counters
    assert eng._decode_fn._cache_size() == 1
    assert eng._prefill_fn._cache_size() == 1


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else (v,)):
            if hasattr(sub, "eqns"):
                yield sub
            elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                yield sub.jaxpr


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


@pytest.mark.parametrize("program", ["decode", "prefill", "prefill-rows"])
def test_pool_is_updated_in_place_not_rebuilt(tiny, program):
    """The step programs pass each KV pool through as ONE buffer: the
    layer loop only reads it (it is never a scan output, carried or
    stacked), every write into it brings only the call's new rows
    ([B, L, Hkv, D] elements a layer, not a layer's [B, M, Hkv, D]),
    and the donated input buffer is the output buffer. Fails if the
    cached forward goes back to slicing a layer out, rewriting it and
    stacking the layers into a new pool (2.68 GB moved about every
    decode step at 7B). The program of a step that carries a tile holds
    two kinds of pool: the tile's scratch ("prefill") and the slots',
    whose decode rows ride behind the tile ("prefill-rows")."""
    import jax.numpy as jnp
    mcfg, model, params = tiny
    eng = _engine(model, params, n_slots=3)
    chunk = eng._prefill_tiles[-1]
    if program == "decode":
        fn, pools = eng._decode_fn, (eng._slots.k, eng._slots.v)
        args = (eng.params, *pools, eng._carry, np.ones((3,), np.int32))
        rows, new_len, first = 3, 1, 1
    else:
        shape = eng._slots.scratch_shapes["k"]
        scratch = (jnp.zeros(shape, jnp.float32),
                   jnp.ones(shape, jnp.float32))
        slots = (eng._slots.k, eng._slots.v)
        fn = eng._prefill_fn
        args = (eng.params, *scratch, *slots, eng._carry,
                eng._tile_args(chunk, np.zeros((chunk,), np.int32), 8, 0,
                               False, 0.0, [0, 1, 2]))
        pools, first = ((scratch, 2) if program == "prefill"
                        else (slots, 4))
        rows, new_len = (1, chunk) if program == "prefill" else (3, 1)
    pool_shape = pools[0].shape
    new_rows = rows * new_len * mcfg.n_kv_heads * mcfg.head_dim
    traced = fn.trace(*args)

    def is_pool(var):
        return getattr(var.aval, "shape", None) == pool_shape

    writes = 0
    for eqn in _walk_eqns(traced.jaxpr.jaxpr):
        name = eqn.primitive.name
        if name == "scan":
            assert not any(map(is_pool, eqn.outvars)), \
                "the pool comes out of the layer loop again"
        if not any(map(is_pool, eqn.outvars)):
            continue
        if name in ("scatter", "dynamic_update_slice"):
            update = eqn.invars[2 if name == "scatter" else 1]
            assert update.aval.size == mcfg.n_layers * new_rows, eqn
            writes += 1
        else:       # a call that only passes the pool through
            assert list(_sub_jaxprs(eqn)), \
                f"{name} makes a new pool-shaped array"
    assert writes == 2          # K and V, once each for all layers
    # both pools are donated and aliased to the outputs ...
    dims = "x".join(map(str, pool_shape))
    assert len(re.findall(rf"tensor<{dims}xf32> {{tf.aliasing_output = ",
                          traced.lower().as_text())) == 2
    # ... and the buffers that come back are the ones that went in
    before = [p.unsafe_buffer_pointer() for p in pools]
    out = fn(*args)
    assert [out[first].unsafe_buffer_pointer(),
            out[first + 1].unsafe_buffer_pointer()] == before
    assert all(p.is_deleted() for p in pools)


def test_deadline_expires_queued_request(tiny):
    """A request still queued past its deadline fails with
    finish_reason='deadline' instead of occupying a slot."""
    _, model, params = tiny
    eng = _engine(model, params)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 128, 8)
    hold = [eng.submit(prompt, max_new_tokens=500) for _ in range(2)]
    for _ in range(4):
        eng.step()                       # admit the holders
    hd = eng.submit(prompt, max_new_tokens=5, deadline_s=0.05)
    time.sleep(0.1)
    eng.step()
    assert hd.finish_reason == "deadline"
    for h in hold:
        h.cancel()
    eng.step()
    assert eng.stats()["slots_free"] == 2


def test_background_loop_streams_tokens(tiny):
    """start()/stop() loop mode: tokens stream to a consumer thread as
    they are generated; stop() fails whatever is still in flight."""
    _, model, params = tiny
    eng = _engine(model, params).start()
    try:
        rng = np.random.RandomState(6)
        h = eng.submit(rng.randint(0, 128, 6), max_new_tokens=12)
        first = h.next(timeout=30)       # streams while decoding
        rest = h.tokens()
        assert isinstance(first, int) and len(rest) == 11
        assert h.finish_reason == "length"
    finally:
        eng.stop()


def test_scheduler_prefill_budget_caps_per_step_tokens(tiny):
    """plan_prefill never spends more than prefill_budget tokens per
    step, and chunks never exceed the static chunk shape."""
    from ray_tpu.inference import Scheduler
    from ray_tpu.inference.scheduler import Request
    sched = Scheduler(n_slots=4, prefill_budget=10, chunk_size=4)
    for n in (13, 9, 2):
        sched.submit(Request(tokens=np.zeros(n, np.int32)))
    seen = []
    for _ in range(6):
        chunks = sched.plan_prefill()
        if not chunks:
            break
        spent = sum(c.length for c in chunks)
        assert spent <= 10
        assert all(c.length <= 4 for c in chunks)
        seen.append(spent)
        for c in chunks:
            if c.is_last:
                sched.prefill_done(c.state, 1, time.monotonic())
            else:
                sched.advance_prefill(c.state, c.length)
    assert sum(seen) == 13 + 9 + 2


# ==========================================================================
# the leftover of a step's budget: a whole remainder, whole tiles, or nothing
# (scheduler-level: no model)
# ==========================================================================

_T = 8          # the tile of the plans below; chunks of 4


def _plans(sched, submit=(), max_steps=200):
    """Drive `sched` until nothing is left to prefill (a prompt that ends
    has its first token decided and holds its slot from then on); `submit`:
    {step: prompt lengths that arrive before that step's plan} -> one
    {rid: [(offset, length, ends its prompt)]} a plan, the chunks joined
    into a dispatch's spans by the engine's own
    `InferenceEngine._prefill_spans`."""
    import types

    from ray_tpu.inference import InferenceEngine
    from ray_tpu.inference.scheduler import Request
    eng = types.SimpleNamespace(_prefill_tiles=(sched.tile,))
    submit, plans = dict(submit), []
    for step in range(max_steps):
        for n in submit.pop(step, ()):
            sched.submit(Request(tokens=np.arange(n) % 128))
        if not (submit or sched._queue or sched._prefilling):
            return plans
        spans = {}
        for c in InferenceEngine._prefill_spans(eng, sched.plan_prefill()):
            spans.setdefault(c.state.rid, []).append(
                (c.start, c.length, c.is_last))
            if c.is_last:
                sched.prefill_done(c.state, 1, time.monotonic())
            else:
                sched.advance_prefill(c.state, c.length)
        plans.append(spans)
    raise AssertionError("the plans did not end")


def _spans_of(plans, rid):
    return [span for plan in plans for span in plan.get(rid, ())]


def _holds_the_invariant(sched, plans, lengths):
    """Every plan is within the budget, every span that does not end its
    prompt is a whole tile, a prompt costs ceil(R / T) dispatches, and
    prompts end in the order they came."""
    for plan in plans:
        assert sum(n for own in plan.values()
                   for _, n, _ in own) <= sched.prefill_budget
    ends = []
    for rid, n in enumerate(lengths):
        own = _spans_of(plans, rid)
        assert [last for _, _, last in own] == [False] * (len(own) - 1) + \
            [True]
        assert all(m == sched.tile for _, m, _ in own[:-1]), own
        assert len(own) == -(-n // sched.tile), (n, own)
        # the cut is the prompt's own: tile by tile from its start
        assert [(o, m) for o, m, _ in own] == [
            (o, min(sched.tile, n - o)) for o in range(0, n, sched.tile)]
        ends.append(next(i for i, plan in enumerate(plans)
                         if any(last for _, _, last in plan.get(rid, ()))))
    assert ends == sorted(ends)


@pytest.mark.parametrize("budget", [8, 16, 20])
@pytest.mark.parametrize("n", [1, 7, 8, 9, 16, 17, 23, 40])
def test_a_prompt_costs_the_tiles_of_its_length_under_co_traffic(budget, n):
    """Behind a prompt that ends mid-budget (every leftover from a token
    to all but one), beside a queue that stands behind it, a prompt of n
    tokens is cut tile by tile from its own start: ceil(n / T) dispatches,
    and no span that is neither a whole tile nor the prompt's end."""
    from ray_tpu.inference import Scheduler
    for ahead in (1, 3, 4, 5, 7, 9, 12, 15, 19, 21):
        sched = Scheduler(n_slots=4, prefill_budget=budget, chunk_size=4,
                          tile=_T)
        lengths = (ahead, n, 13, 2)
        plans = _plans(sched, {0: lengths})
        _holds_the_invariant(sched, plans, lengths)
        assert len(_spans_of(plans, 1)) == -(-n // _T)


@pytest.mark.parametrize("ahead,n", [(3, 5), (5, 3), (1, 7), (4, 4), (7, 1),
                                     (11, 5), (18, 6)])
def test_a_prompt_that_fits_whole_still_shares_the_step(ahead, n):
    """What the budget has left when a prompt ends goes to the next one
    where it ends that one too (short prompts share a step as ever)."""
    from ray_tpu.inference import Scheduler
    sched = Scheduler(n_slots=4, prefill_budget=8, chunk_size=4, tile=_T)
    plans = _plans(sched, {0: (ahead, n)})
    shared = next(plan for plan in plans if 1 in plan)
    assert set(shared) == {0, 1} and shared[1] == [(0, n, True)]
    assert len(plans) == -(-ahead // 8) and sched.prefill_deferred == 0


@pytest.mark.parametrize("ahead,n,behind", [(3, 20, 2), (5, 6, 1),
                                            (7, 9, 1), (12, 5, 3)])
def test_a_deferred_prompt_is_first_in_the_next_plan_and_keeps_fifo(
        ahead, n, behind):
    """A prompt the leftover cannot finish stays QUEUED, its slot not
    taken; nothing behind it passes it, even what the leftover would
    hold whole; it is the first the next plan serves, with the budget
    whole."""
    from ray_tpu.inference import Scheduler
    from ray_tpu.inference.scheduler import Request
    sched = Scheduler(n_slots=4, prefill_budget=8, chunk_size=4, tile=_T)
    for m in (ahead, n, behind):
        sched.submit(Request(tokens=np.arange(m)))
    for _ in range(-(-ahead // 8) - 1):
        (c0, *_), = [sched.plan_prefill()]
        sched.advance_prefill(c0.state, 8)
    chunks = sched.plan_prefill()
    assert {c.state.rid for c in chunks} == {0} and chunks[-1].is_last
    assert sched.prefill_deferred == 1
    assert [st.rid for st in sched._queue] == [1, 2]
    assert sched._queue[0].slot is None and sched.occupancy() == 1
    sched.prefill_done(chunks[0].state, 1, time.monotonic())
    chunks = sched.plan_prefill()
    assert chunks[0].state.rid == 1 and chunks[0].start == 0
    assert sum(c.length for c in chunks if c.state.rid == 1) == min(n, 8)
    # the one behind rides only where the deferred one ended first
    assert ({c.state.rid for c in chunks} == {1, 2}) == (n + behind <= 8)
    assert sched.prefill_deferred == 1 + (n <= 8 < n + behind)


@pytest.mark.parametrize("hit,n,shares", [(16, 20, True), (16, 21, True),
                                          (12, 20, False), (0, 20, False),
                                          (16, 22, False)])
def test_a_queued_prompts_remainder_is_counted_behind_its_prefix_hit(
        hit, n, shares):
    """A leftover of 5: a prompt of n tokens whose first `hit` lie in the
    prefix cache shares the step where n - hit <= 5 and waits where not,
    and the look that decides it pins nothing and counts no lookup."""
    from ray_tpu.inference import Scheduler
    from ray_tpu.inference.prefix_cache import RadixPrefixCache
    from ray_tpu.inference.scheduler import Request
    cache = RadixPrefixCache(4, 16)
    prompt = (np.arange(n) * 7 + 1) % 128
    cache.insert([int(t) for t in prompt[:hit]])
    sched = Scheduler(n_slots=4, prefill_budget=8, chunk_size=4, tile=_T,
                      prefix_cache=cache)
    sched.submit(Request(tokens=np.full(3, 127)))
    h = sched.submit(Request(tokens=prompt))
    chunks = sched.plan_prefill()
    own = [c for c in chunks if c.state.rid == h.rid]
    assert bool(own) == shares and sched.prefill_deferred == (not shares)
    if shares:
        assert own[0].start == hit and own[-1].is_last
        assert h.prefix_matched == hit
        return
    assert cache.lookups == 1                   # the one ahead of it
    nodes = cache.walk(prompt, hit // 4)        # pins them itself
    cache.release(nodes)
    assert len(nodes) == hit // 4 and all(node.pins == 0 for node in nodes)
    sched.prefill_done(chunks[0].state, 1, time.monotonic())
    plans = _plans(sched)
    own = _spans_of(plans, h.rid)
    assert own[0][0] == hit and len(own) == -(-(n - hit) // 8)
    assert all(m == 8 for _, m, _ in own[:-1])


@pytest.mark.parametrize("ahead", [0, 3, 9])
@pytest.mark.parametrize("budget", [16, 20, 24, 40])
def test_a_budget_raised_past_the_tile_gives_whole_tiles(budget, ahead):
    """A budget set past the largest compiled tile at run time (what
    `LLMDeployment.reconfigure` assigns) makes more dispatches a step,
    each a whole tile or a prompt's end; the leftover behind a prompt
    that ended is given in whole tiles too."""
    from ray_tpu.inference import Scheduler
    sched = Scheduler(n_slots=4, prefill_budget=8, chunk_size=4)
    assert sched.tile == _T
    sched.prefill_budget = budget
    lengths = (ahead, 45, 6) if ahead else (45, 6)
    plans = _plans(sched, {0: lengths})
    _holds_the_invariant(sched, plans, lengths)
    first = plans[0].get(len(lengths) - 2, [])
    assert len(first) == (budget - ahead) // _T
    assert all(span[1] == _T for span in first)


@pytest.mark.parametrize("case,deferred", [
    ("withheld", 1), ("fits", 0), ("budget_spent", 0), ("no_free_slot", 0),
    ("held", 0), ("behind_a_held_one", 1), ("nothing_waits", 0)])
def test_prefill_deferred_counts_the_plans_that_withheld_a_leftover(
        case, deferred):
    """One a plan, and only where a prompt that could have started (a
    free slot, not held) was given none of a leftover that was there."""
    from ray_tpu.inference import Scheduler
    from ray_tpu.inference.scheduler import Request
    sched = Scheduler(n_slots=1 if case == "no_free_slot" else 4,
                      prefill_budget=8, chunk_size=4, tile=_T)
    ahead = 8 if case == "budget_spent" else 3
    sched.submit(Request(tokens=np.arange(ahead)))
    if case == "behind_a_held_one":
        sched.submit(Request(tokens=np.arange(2)), hold=True)
    if case != "nothing_waits":
        sched.submit(Request(tokens=np.arange(4 if case == "fits" else 9)),
                     hold=case == "held")
    chunks = sched.plan_prefill()
    assert sched.prefill_deferred == deferred
    assert {c.state.rid for c in chunks} == ({0, 1} if case == "fits"
                                             else {0})
    # the next plan serves it first and withholds nothing
    sched.prefill_done(chunks[0].state, 1, time.monotonic())
    sched.plan_prefill()
    assert sched.prefill_deferred == deferred


def test_a_budget_below_the_tile_gives_the_first_prompt_the_budget():
    """Budget 10 in chunks of 4 is a tile of 12: no plan could give a
    whole tile, so the first prompt a plan serves gets the budget and
    every other only what ends it."""
    from ray_tpu.inference import Scheduler
    sched = Scheduler(n_slots=4, prefill_budget=10, chunk_size=4)
    assert sched.tile == 12
    plans = _plans(sched, {0: (13, 9, 2)})
    assert [{rid: sum(n for _, n, _ in own) for rid, own in plan.items()}
            for plan in plans] == [{0: 10}, {0: 3}, {1: 9}, {2: 2}]
    assert sched.prefill_deferred == 2


# ==========================================================================
# prefill tiles: what a step gives one request runs as one dispatch
# ==========================================================================

def test_prefill_tile_family():
    """The budget in whole chunks, and below it quarters while they are
    a chunk or more: a handful however large the ratio, one member
    where the budget is under four chunks (the cells' 128 / 256, and
    the defaults, budget == chunk, where nothing changes)."""
    from ray_tpu.inference.engine import prefill_tiles
    assert prefill_tiles(64, 64) == (64,)
    assert prefill_tiles(64, 32) == (64,)
    assert prefill_tiles(128, 256) == (256,)
    assert prefill_tiles(4, 8) == (8,)
    assert prefill_tiles(4, 12) == prefill_tiles(4, 10) == (12,)
    assert prefill_tiles(4, 16) == (4, 16)
    assert prefill_tiles(4, 40) == (12, 40)
    assert prefill_tiles(128, 1024) == (256, 1024)
    assert prefill_tiles(16, 2048) == (32, 128, 512, 2048)


@pytest.fixture(scope="module")
def tiny_moe(jax_cpu):
    """Dropless MoE (capacity_factor = E/K: an expert's capacity is its
    group's length, whatever the tile)."""
    return _tiny_model(jax_cpu, seed=1, n_experts=4, expert_top_k=2,
                       capacity_factor=2.0)


@pytest.fixture(scope="module")
def tile_engines(tiny, tiny_moe):
    """(kind, budget) -> a one-slot engine at chunk 4, built once: the
    parity cases below only send requests. Budget 4 is the
    chunk-by-chunk path (one tile, today's dispatches)."""
    built = {}

    def get(kind, budget):
        if (kind, budget) not in built:
            _, model, params = tiny if kind == "dense" else tiny_moe
            built[kind, budget] = _engine(model, params, n_slots=1,
                                          prefill_budget=budget)
        return built[kind, budget]
    return get


def _prefill_then_decode(eng, prompt, n_new):
    """Greedy tokens of one request, and the K/V its prefill left in
    slot 0 (read when the first token is out, before decode adds)."""
    h = eng.submit(prompt, max_new_tokens=n_new)
    assert _run_until(eng, lambda: h.first_token_t is not None, 40)
    n = len(prompt)
    kv = (np.asarray(eng._slots.k[:, 0, :n]),
          np.asarray(eng._slots.v[:, 0, :n]))
    assert _run_until(eng, lambda: h.finish_reason is not None)
    return h.tokens(), kv


@pytest.fixture(scope="module")
def uncached_greedy():
    """(model, params, prompt, toks) -> what the uncached forward would
    have decoded greedily after prompt + toks[:i], for every i: one
    causal pass over the sequence padded to a fixed length (one compile
    a model)."""
    import jax
    import jax.numpy as jnp
    compiled = {}

    def greedy(model, params, prompt, toks):
        if id(model) not in compiled:
            def fwd(params, seq):
                out = model.apply({"params": params}, seq)
                return jnp.argmax(
                    out[0] if isinstance(out, tuple) else out, -1)
            compiled[id(model)] = jax.jit(fwd)
        seq = np.zeros((1, 48), np.int32)
        full = list(prompt) + list(toks[:-1])
        seq[0, :len(full)] = full
        got = np.asarray(compiled[id(model)](params, jnp.asarray(seq)))[0]
        return [int(t) for t in got[len(prompt) - 1:len(full)]]
    return greedy


# prompt lengths on both sides of every tile edge (8 / 4, 16) and chunk,
# and prompts of several steps whose last span is short, exact and long
_TILE_CASES = [(8, n) for n in (1, 3, 4, 5, 7, 8, 9, 12, 13, 16, 23)] + \
              [(16, n) for n in (3, 4, 5, 7, 8, 9, 15, 16, 17, 24, 25, 33,
                                 40)]


@pytest.mark.parametrize("kind", ["dense", "moe"])
@pytest.mark.parametrize("budget,n", _TILE_CASES)
def test_tiled_prefill_matches_chunks_and_uncached(tiny, tiny_moe,
                                                   tile_engines,
                                                   uncached_greedy, kind,
                                                   budget, n):
    """A prompt prefilled through tiles leaves the K/V the
    chunk-by-chunk path leaves and decodes the tokens of the uncached
    forward (float32 on the CPU)."""
    _, model, params = tiny if kind == "dense" else tiny_moe
    prompt = np.random.RandomState(100 * budget + n).randint(0, 128, n)
    toks, (k, v) = _prefill_then_decode(tile_engines(kind, budget),
                                        prompt, 5)
    toks0, (k0, v0) = _prefill_then_decode(tile_engines(kind, 4),
                                           prompt, 5)
    np.testing.assert_allclose(k, k0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v, v0, rtol=1e-5, atol=1e-5)
    assert toks == toks0
    assert toks == uncached_greedy(model, params, prompt, toks)


@pytest.mark.parametrize("budget", [8, 16])
def test_one_prefill_dispatch_per_request_per_step(tiny, budget):
    """A step never runs more than prefill_budget prompt tokens and
    makes exactly one dispatch for each request it gives prompt tokens
    to; prefill_tokens counts real tokens, not padding."""
    _, model, params = tiny
    eng = _engine(model, params, n_slots=4, prefill_budget=budget)
    planned = []                  # per step: {rid: tokens}, as planned
    plan = eng.sched.plan_prefill

    def spy():
        chunks = plan()
        per = {}
        for c in chunks:
            per[c.state.rid] = per.get(c.state.rid, 0) + c.length
        planned.append(per)
        return chunks
    eng.sched.plan_prefill = spy
    rng = np.random.RandomState(11)
    lens = (19, 3, 9, 26, 5, 14)
    hs = [eng.submit(rng.randint(0, 128, n), max_new_tokens=3)
          for n in lens]
    seen = []
    while not all(h.finish_reason for h in hs):
        d0, t0 = eng.prefill_dispatches, eng.prefill_tokens
        eng.step()
        seen.append((eng.prefill_dispatches - d0, eng.prefill_tokens - t0))
        assert len(seen) < 300
    assert len(planned) == len(seen)
    for per, (dispatches, tokens) in zip(planned, seen):
        assert tokens == sum(per.values()) <= budget
        assert dispatches == len(per)
    assert any(len(per) > 1 for per in planned)      # a shared step
    assert any(max(per.values(), default=0) > 4 for per in planned)
    st = eng.stats()
    assert st["prefill_tokens"] == sum(lens)
    assert st["prefill_dispatches"] == sum(d for d, _ in seen)
    # a prompt costs the tiles of its length, whatever shared its steps
    assert st["prefill_dispatches"] == sum(-(-n // budget) for n in lens)
    assert st["prefill_deferred"] > 0


def _tiles_and_tokens(eng, fillers, prompt, n_new=4):
    """Tokens of `prompt` submitted behind `fillers` (which share its
    steps' budget), its K/V when its first token is out, and the
    (tile, offset, real tokens) of every prefill dispatch it got."""
    from ray_tpu.inference.engine import _TILE_HEAD
    own, cur = [], {}
    run, fn = eng._issue_prefill, eng._prefill_fn
    S = eng.config.n_slots

    def spy_run(ch, *rest):
        cur["st"] = ch.state
        return run(ch, *rest)

    def spy_fn(*args):
        host = args[-1]             # the host's one array: _tile_args
        if cur["st"].handle is h:
            cur["slot"] = cur["st"].slot
            own.append((len(host) - S - _TILE_HEAD, int(host[S]),
                        int(host[S + 1])))
        return fn(*args)
    rng = np.random.RandomState(len(prompt))
    hs = [eng.submit(rng.randint(0, 128, n), max_new_tokens=1)
          for n in fillers]
    h = eng.submit(prompt, max_new_tokens=n_new)
    eng._issue_prefill, eng._prefill_fn = spy_run, spy_fn
    try:
        assert _run_until(eng, lambda: h.first_token_t is not None, 60)
    finally:
        eng._issue_prefill, eng._prefill_fn = run, fn
    n, slot = len(prompt), cur["slot"]
    kv = (np.asarray(eng._slots.k[:, slot, :n]),
          np.asarray(eng._slots.v[:, slot, :n]))
    assert _run_until(eng, lambda: all(
        x.finish_reason for x in (h, *hs)))
    assert sum(n_real for _, _, n_real in own) == n
    return h.tokens(), kv, own


@pytest.mark.parametrize("kind", ["dense", "moe"])
@pytest.mark.parametrize("n,tile", [(3, 4), (4, 4), (5, 16), (8, 16),
                                    (9, 16), (16, 16), (17, 16),
                                    (37, 16)])
def test_a_prompts_tile_does_not_depend_on_co_traffic(tiny, tiny_moe, kind,
                                                      n, tile):
    """Every span of a prompt runs in the tile of the prompt's length,
    and the spans are the prompt's own: behind co-traffic that ends
    mid-budget (a leftover of 13, of 3, of 5 behind a prompt of two
    steps) it is cut at the same offsets into the same lengths as served
    alone, so the same programs compute the same K/V bits (on the chip
    two tiles, or two cuts, differ in a last bit, and a near-tie then
    decodes another token)."""
    _, model, params = tiny if kind == "dense" else tiny_moe
    eng = _engine(model, params, n_slots=3, prefill_budget=16)
    prompt = np.random.RandomState(300 + n).randint(0, 128, n)
    alone, kv0, own0 = _tiles_and_tokens(eng, (), prompt)
    assert {t for t, _, _ in own0} == {tile}
    assert len(own0) == -(-n // 16)
    deferred = 0
    for fillers in ((3,), (13,), (21, 6)):
        d0 = eng.stats()["prefill_deferred"]
        toks, kv, own = _tiles_and_tokens(eng, fillers, prompt)
        assert own == own0          # (tile, offset, real tokens) a span
        assert toks == alone
        np.testing.assert_array_equal(kv[0], kv0[0])
        np.testing.assert_array_equal(kv[1], kv0[1])
        deferred += eng.stats()["prefill_deferred"] - d0
    # where a leftover could not end it, it waited for the next step
    assert deferred >= (n > 13) + (n > 3) + (n > 5)


def test_prefill_tiles_compile_before_first_submit_and_never_again(tiny):
    """Every tile is compiled when the engine is built; mixed traffic
    and a budget raised at run time (LLMDeployment.reconfigure does
    exactly this assignment) use those shapes and no other: a span is
    capped at the largest tile and the step makes more dispatches."""
    _, model, params = tiny
    eng = _engine(model, params, n_slots=2, prefill_budget=16)
    assert eng._prefill_tiles == (4, 16)
    assert eng.prefill_compile_count == 2
    assert eng._prefill_fn._cache_size() == 2
    rng = np.random.RandomState(12)
    hs = [eng.submit(rng.randint(0, 128, n), max_new_tokens=2)
          for n in (1, 4, 6, 11, 16, 17, 30, 41)]
    assert _run_until(eng, lambda: all(h.finish_reason for h in hs))
    assert eng.prefill_compile_count == 2
    eng.sched.prefill_budget = 40
    want = eng.submit(np.arange(45) % 128, max_new_tokens=4)
    d0, t0 = eng.prefill_dispatches, eng.prefill_tokens
    eng.step()
    # a budget of 40 gives one request two whole tiles: spans of 16 + 16
    assert eng.prefill_dispatches - d0 == 2
    assert eng.prefill_tokens - t0 == 32
    assert _run_until(eng, lambda: want.finish_reason is not None)
    assert eng.prefill_compile_count == 2
    assert eng._prefill_fn._cache_size() == 2
    assert eng.decode_compile_count == 1
    ref = _engine(model, params, prefill_budget=4)
    h = ref.submit(np.arange(45) % 128, max_new_tokens=4)
    assert _run_until(ref, lambda: h.finish_reason is not None)
    assert want.tokens() == h.tokens()
    # budget == chunk (the defaults): one tile, today's dispatches
    assert ref._prefill_tiles == (4,) and ref.prefill_compile_count == 1
    assert ref.prefill_dispatches == 12 and ref.prefill_tokens == 45


def test_int8_prefix_cache_keeps_single_chunk_dispatches(tiny):
    """kv_quant="int8" with a prefix cache writes each completed chunk
    through and reloads its dequantised values before the next chunk
    attends it, so the chunk stays the only tile there and a hit equals
    the miss that filled it. Either setting alone tiles."""
    _, model, params = tiny
    eng = _engine(model, params, prefill_budget=16, kv_quant="int8",
                  prefix_cache_slots=1)
    assert eng._prefill_tiles == (4,) and eng.prefill_compile_count == 1
    prompt = np.random.RandomState(13).randint(0, 128, 26)
    miss = eng.submit(prompt, max_new_tokens=8)
    assert _run_until(eng, lambda: miss.finish_reason is not None)
    assert eng.prefill_dispatches == 7          # ceil(26 / 4)
    hit = eng.submit(prompt, max_new_tokens=8)
    assert _run_until(eng, lambda: hit.finish_reason is not None)
    assert hit.prefix_matched == 24
    assert hit.tokens() == miss.tokens()
    assert eng.prefill_compile_count == 1
    assert _engine(model, params, prefill_budget=16, prefix_cache_slots=1
                   )._prefill_tiles == (4, 16)


def test_no_prefill_executable_after_build_on_a_mesh(tiny, jax_cpu):
    """On a mesh a tile has two executables, one for a new scratch and
    one for the scratch a prefill handed back (their shardings differ):
    the engine compiles both when it is built, and prompts of one span,
    of several, and with a prefix hit add none."""
    from ray_tpu.serve.sharded import (ShardedEngineReplica,
                                       default_serving_mesh)
    if len(jax_cpu.devices()) < 2:
        pytest.skip("needs a mesh of several devices")
    _, model, params = tiny
    rep = ShardedEngineReplica(
        model, n_slots=2, max_len=64, prefill_chunk=4, prefill_budget=16,
        prefix_cache_slots=1, params_fn=lambda: params, seed=0,
        mesh=default_serving_mesh(jax_cpu.devices()))
    eng = rep.engine
    built = eng._prefill_fn._cache_size()
    assert eng.prefill_compile_count == len(eng._prefill_tiles) == 2
    rng = np.random.RandomState(15)
    shared = [int(t) for t in rng.randint(0, 128, 13)]
    for prompt in ([5, 6, 7], shared, shared + [1, 2, 3, 4, 5, 6],
                   [int(t) for t in rng.randint(0, 128, 30)]):
        assert len(rep.generate(prompt, max_new_tokens=3)) == 3
    assert eng.stats()["prefix_hits"] >= 1
    assert eng._prefill_fn._cache_size() == built
    assert eng.prefill_compile_count == 2
