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


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, TransformerLM
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)
    model = TransformerLM(cfg)
    params = model.init(jax_cpu.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


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
    assert eng.prefill_compile_count == 1
    # the jit caches agree with the trace counters
    assert eng._decode_fn._cache_size() == 1


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


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_pool_is_updated_in_place_not_rebuilt(tiny, program):
    """The step programs pass each KV pool through as ONE buffer: the
    layer loop only reads it (it is never a scan output, carried or
    stacked), every write into it brings only the call's new rows
    ([B, L, Hkv, D] elements a layer, not a layer's [B, M, Hkv, D]),
    and the donated input buffer is the output buffer. Fails if the
    cached forward goes back to slicing a layer out, rewriting it and
    stacking the layers into a new pool (2.68 GB moved about every
    decode step at 7B)."""
    import jax.numpy as jnp
    mcfg, model, params = tiny
    eng = _engine(model, params, n_slots=3)
    chunk = eng.config.prefill_chunk
    if program == "decode":
        fn, pools = eng._decode_fn, (eng._pool_k, eng._pool_v)
        args = (eng.params, *pools, eng._lengths, eng._last_tok,
                eng._rng, eng._temps)
        rows, new_len = 3, 1
    else:
        shape = (mcfg.n_layers, 1, eng.config.max_len + chunk,
                 mcfg.n_kv_heads, mcfg.head_dim)
        fn, pools = eng._prefill_fn, (jnp.zeros(shape, jnp.float32),
                                      jnp.ones(shape, jnp.float32))
        args = (eng.params, *pools, jnp.zeros((1, chunk), jnp.int32),
                jnp.int32(8), jnp.int32(chunk), eng._rng,
                jnp.float32(0.0))
        rows, new_len = 1, chunk
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
    assert [out[1].unsafe_buffer_pointer(),
            out[2].unsafe_buffer_pointer()] == before
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
