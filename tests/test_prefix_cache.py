"""Radix prefix KV cache (ray_tpu/inference/prefix_cache.py) + coalesced
token streaming (PR 10): trie insert/longest-match/ref-count/LRU units,
greedy bit-exact hit-vs-miss parity through the engine, the compile-once
contract with the cache on, coalesced-stream exactly-once semantics
(including resume mid-coalesced-chunk under replica death), and session
affinity routing.

The first part is cluster-free; the cluster tier follows it."""

import time

import numpy as np
import pytest


# --------------------------------------------------------------------------
# trie units (pure host code, no JAX)
# --------------------------------------------------------------------------

def _cache(chunk=4, blocks=8):
    from ray_tpu.inference import RadixPrefixCache
    return RadixPrefixCache(chunk, blocks)


def test_trie_insert_and_longest_match():
    c = _cache(chunk=4, blocks=8)
    toks = list(range(40, 53))              # 13 tokens = 3 full chunks
    created = c.insert(toks)
    assert [off for off, _ in created] == [0, 4, 8]
    # longest match walks the chunk path; capped BELOW the prompt length
    m, nodes = c.match(toks)
    assert m == 12 and len(nodes) == 3      # 13 tokens: last one prefills
    c.release(nodes)
    # a 12-token prompt with the same prefix may match at most 8 (cap)
    m, nodes = c.match(toks[:12])
    assert m == 8
    c.release(nodes)
    # diverging suffix matches only the shared chunks
    m, nodes = c.match(toks[:8] + [99, 98, 97, 96, 95])
    assert m == 8
    c.release(nodes)
    # diverging FIRST chunk matches nothing
    m, nodes = c.match([99] + toks[1:])
    assert m == 0 and nodes == []
    # re-insert of cached chunks allocates nothing new
    assert c.insert(toks) == []
    # extension allocates only the new chunk
    created = c.insert(toks[:12] + [7, 7, 7, 7])
    assert [off for off, _ in created] == [12]


def test_trie_refcount_blocks_eviction_lru_under_pressure():
    c = _cache(chunk=2, blocks=2)
    c.insert([1, 2])                        # block A (oldest stamp)
    c.insert([3, 4])                        # block B
    # pool exhausted: next insert must evict the LRU leaf (A)
    created = c.insert([5, 6])
    assert len(created) == 1 and c.evictions == 1
    assert c.match([1, 2, 9])[0] == 0       # A is gone
    # pin B (an in-flight request matched it): under pressure only the
    # UNPINNED leaves cycle; B survives arbitrarily many evictions
    m, pinned = c.match([3, 4, 9])
    assert m == 2
    c.insert([7, 8])                        # evicts [5,6]
    c.insert([9, 10])                       # evicts [7,8]
    assert c.evictions == 3
    assert c.match([3, 4, 1])[0] == 2       # B still matchable
    c.release(pinned)


def test_trie_pinned_never_evicted_explicitly():
    c = _cache(chunk=2, blocks=1)
    c.insert([1, 2])
    m, nodes = c.match([1, 2, 3])
    assert m == 2
    # the only block is pinned: allocation for a new chunk must fail
    # (insert returns nothing) rather than reuse pinned memory
    assert c.insert([5, 6]) == []
    c.release(nodes)
    assert len(c.insert([5, 6])) == 1       # unpinned -> evictable
    assert c.evictions == 1


def test_trie_interior_nodes_not_evicted_before_leaves():
    c = _cache(chunk=2, blocks=3)
    c.insert([1, 2, 3, 4, 5, 6])            # chain of 3 nodes
    # pressure: the leaf (5,6) must go first, never the root chunk
    created = c.insert([9, 9])
    assert len(created) == 1
    assert c.match([1, 2, 3, 4, 9])[0] == 4  # interior chain survives


# --------------------------------------------------------------------------
# engine integration: parity + compile-once
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, TransformerLM
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _engine(model, params, **kw):
    from ray_tpu.inference import EngineConfig, InferenceEngine
    cfg = dict(n_slots=2, max_len=48, prefill_chunk=4, prefill_budget=8)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg))


def _drain(eng, handle, max_steps=300):
    for _ in range(max_steps):
        eng.step()
        if handle.finish_reason is not None:
            return handle.tokens()
    raise AssertionError("request did not finish")


def test_greedy_bit_exact_hit_vs_miss_vs_uncached(tiny):
    """The acceptance contract: greedy output is bit-identical whether
    the prompt's prefix prefilled from scratch (miss), restored from
    cached blocks (hit), or ran through a cache-disabled engine."""
    _, model, params = tiny
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, 128, 17)
    eng_off = _engine(model, params)
    want = _drain(eng_off, eng_off.submit(prompt, max_new_tokens=10))
    eng = _engine(model, params, prefix_cache_slots=1)
    h_miss = eng.submit(prompt, max_new_tokens=10)
    miss = _drain(eng, h_miss)
    h_hit = eng.submit(prompt, max_new_tokens=10)
    hit = _drain(eng, h_hit)
    assert h_miss.prefix_matched == 0
    assert h_hit.prefix_matched == 16       # 17 tokens, cap leaves 1
    assert miss == want and hit == want
    # a longer prompt sharing the prefix also matches and stays exact
    prompt2 = np.concatenate([prompt, rng.randint(0, 128, 9)])
    eng_off2 = _engine(model, params)
    want2 = _drain(eng_off2, eng_off2.submit(prompt2, max_new_tokens=10))
    h2 = eng.submit(prompt2, max_new_tokens=10)
    assert _drain(eng, h2) == want2
    assert h2.prefix_matched == 16


def test_decode_compiles_exactly_once_with_cache_on(tiny):
    """Hits, misses, evictions and block restores never retrace any of
    the engine's programs — the copy fns are fixed-shape too."""
    _, model, params = tiny
    eng = _engine(model, params, prefix_cache_slots=1)
    rng = np.random.RandomState(8)
    shared = rng.randint(0, 128, 12)
    hs = []
    for i in range(6):
        p = np.concatenate([shared, rng.randint(0, 128, 1 + i)])
        hs.append(eng.submit(p, max_new_tokens=4))
    for _ in range(400):
        eng.step()
        if all(h.finish_reason for h in hs):
            break
    assert all(h.finish_reason for h in hs)
    st = eng.stats()
    assert st["prefix_hits"] >= 4, st
    assert eng.decode_compile_count == 1
    # the prefill family (chunk 4 / budget 8: the one tile of 8)
    # compiled when the engine was built and has not grown
    assert eng.prefill_compile_count == len(eng._prefill_tiles) == 1
    assert eng._prefill_fn._cache_size() == 1
    assert eng._decode_fn._cache_size() == 1
    assert eng._blocks._load_fn._cache_size() == 1
    assert eng._blocks._save_fn._cache_size() == 1


def test_cache_eviction_under_slot_pressure_keeps_serving(tiny):
    """A block pool much smaller than the working set evicts LRU and
    keeps producing exact output (hits just get rarer)."""
    _, model, params = tiny
    eng = _engine(model, params, prefix_cache_slots=1, max_len=16,
                  prefill_chunk=4)           # 4 blocks total
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 128, 9) for _ in range(5)]
    for p in prompts + prompts:
        h = eng.submit(p, max_new_tokens=3)
        _drain(eng, h)
    st = eng.stats()
    assert st["prefix_evictions"] > 0
    assert eng.decode_compile_count == 1
    # exactness after heavy eviction churn
    eng_off = _engine(model, params, max_len=16, prefill_chunk=4)
    want = _drain(eng_off, eng_off.submit(prompts[0], max_new_tokens=3))
    assert _drain(eng, eng.submit(prompts[0], max_new_tokens=3)) == want


# --------------------------------------------------------------------------
# coalesced streaming: RequestHandle.next_many + handle-layer unpack
# --------------------------------------------------------------------------

def test_next_many_coalesces_and_never_drops_the_tail(tiny):
    """next_many returns >= 1 token per call, caps at max_tokens, and a
    finish mid-batch delivers the collected tokens NOW with
    StopIteration only on the following call."""
    _, model, params = tiny
    eng = _engine(model, params).start()
    try:
        h = eng.submit(np.arange(1, 6), max_new_tokens=11)
        got = [h.next(timeout=30)]          # eager first token
        batches = []
        while True:
            try:
                b = h.next_many(4, flush_s=0.05, timeout=30)
            except StopIteration:
                break
            assert 1 <= len(b) <= 4
            batches.append(b)
            got.extend(b)
        assert len(got) == 11
        assert h.finish_reason == "length"
    finally:
        eng.stop()


def test_llm_deployment_streams_coalesced_chunks(tiny):
    """Direct-call contract: first chunk is the eager single token; all
    chunks respect stream_coalesce_tokens; flattening equals generate()."""
    cfg, model, params = tiny
    from ray_tpu.inference import LLMDeployment
    dep = LLMDeployment(cfg, n_slots=2, max_len=64, prefill_chunk=4,
                        prefill_budget=8, stream_coalesce_tokens=5,
                        stream_coalesce_ms=15.0,
                        params_fn=lambda: params)
    try:
        chunks = list(dep([1, 2, 3], max_new_tokens=17))
        assert len(chunks[0]) == 1          # TTFT never waits the window
        assert all(len(c) <= 5 for c in chunks)
        flat = [t for c in chunks for t in c]
        assert len(flat) == 17
        assert dep.generate([1, 2, 3], max_new_tokens=17) == flat
        # per-call override down to per-token framing
        singles = list(dep([1, 2, 3], max_new_tokens=5,
                           stream_coalesce_tokens=1))
        assert [len(c) for c in singles] == [1] * 5
    finally:
        dep.engine.stop()


class _StubGen:
    """Stands in for the core ObjectRefGenerator (coalesced frames)."""

    def __init__(self, frames, fail_after_frames=None, error=None):
        self._frames = list(frames)
        self._i = 0
        self._fail = fail_after_frames
        self._error = error
        self.closed = False

    def next(self, timeout=None):
        if self._fail is not None and self._i >= self._fail:
            raise self._error
        if self._i >= len(self._frames):
            raise StopIteration
        v = self._frames[self._i]
        self._i += 1
        return v

    def close(self):
        self.closed = True


def _wrap(stub, **kw):
    from ray_tpu.serve.handle import DeploymentResponseGenerator
    g = DeploymentResponseGenerator(stub, None, 0, **kw)
    g._get = lambda ref: ref
    return g


def test_coalesced_resume_mid_chunk_exactly_once():
    """Replica dies after delivering one full frame and while a second
    is buffered client-side: the resume carries TOKEN-granular state
    (fetched tokens, flattened), the buffered tail still reaches the
    consumer, and the continuation starts at the exact next token —
    zero dropped, zero duplicated."""
    import ray_tpu
    seen = {}

    def resume(fetched, chunks):
        seen["fetched"] = fetched
        seen["chunks"] = list(chunks)
        return _wrap(_StubGen([[50, 60], [70]]), unpack=True), 0

    g = _wrap(_StubGen([[10], [20, 30, 40]], fail_after_frames=2,
                       error=ray_tpu.ActorDiedError("replica gone")),
              unpack=True, resume=resume, record_chunks=True)
    # consume ONE token: [20,30,40] is fetched+buffered when death lands
    assert next(g) == 10
    assert next(g) == 20
    assert list(g) == [30, 40, 50, 60, 70]
    # resume saw every FETCHED token (buffered ones included: they are
    # delivered from the buffer, so the fresh stream continues after)
    assert seen == {"fetched": 4, "chunks": [10, 20, 30, 40]}


def test_coalesced_nonresumable_skip_is_token_granular():
    """Non-resumable restart: the fresh stream re-produces everything
    with DIFFERENT frame boundaries; the wrapper skips exactly the
    fetched token count, keeping a straddling frame's tail."""
    import ray_tpu

    def resume(fetched, chunks):
        assert chunks is None
        return _wrap(_StubGen([[10, 20, 30], [40, 50]]),
                     unpack=True), fetched

    g = _wrap(_StubGen([[10], [20]], fail_after_frames=2,
                       error=ray_tpu.ActorDiedError("gone")),
              unpack=True, resume=resume)
    assert list(g) == [10, 20, 30, 40, 50]


def test_next_batch_drains_frames_without_blocking_per_token():
    g = _wrap(_StubGen([[1, 2, 3], [4]]), unpack=True)
    assert g.next_batch() == [1, 2, 3]
    assert g.next_batch() == [4]
    with pytest.raises(StopIteration):
        g.next_batch()
    # mixed use: __next__ then next_batch drains the remainder
    g = _wrap(_StubGen([[1, 2, 3]]), unpack=True)
    assert next(g) == 1
    assert g.next_batch() == [2, 3]


def test_plain_streams_unchanged_without_unpack():
    """A non-coalesced deployment yielding list VALUES must not be
    unpacked (the flag, not the type, decides)."""
    vals = [{"a": 1}, [9, 9], "x"]
    g = _wrap(_StubGen(vals))
    assert list(g) == vals


# --------------------------------------------------------------------------
# session-affinity routing (ROADMAP 1c first slice)
# --------------------------------------------------------------------------

def _router(n):
    from ray_tpu.serve.handle import _Router
    r = _Router.__new__(_Router)     # skip ctor (no long-poll client)
    import threading
    r.deployment_name = "d"
    r.app_name = "a"
    r.replicas = [object() for _ in range(n)]
    r.inflight = {i: 0 for i in range(n)}
    r.shared_load = {}
    r.version = 0
    r.resumable = False
    r.coalesced = False
    r.prefix_routed = False
    r.replica_ids = []
    r._summaries = {}
    r._summary_chunk = None
    r._last_summary_refresh = time.monotonic() + 1e6
    r.lock = threading.Lock()
    r._last_refresh = time.monotonic() + 1e6   # never refresh
    r.model_map = {}
    return r


def test_session_id_routes_sticky():
    r = _router(4)
    picks = {r.pick(session_id="sess-abc")[0] for _ in range(8)}
    assert len(picks) == 1                  # same session -> same replica
    # sessions spread (crc32 over 64 ids on 4 replicas hits them all)
    spread = {r.pick(session_id=f"s{i}")[0] for i in range(64)}
    assert spread == {0, 1, 2, 3}


def test_session_fallback_least_ongoing_when_sticky_unavailable():
    r = _router(3)
    sticky = r.pick(session_id="user-1")[0]
    r.inflight = {0: 5, 1: 5, 2: 5}
    others = [i for i in range(3) if i != sticky]
    r.inflight[others[0]] = 0               # clearly least-ongoing
    idx, _ = r.pick(session_id="user-1", avoid={sticky})
    assert idx == others[0]


def test_session_rehashes_when_replica_set_shrinks():
    r = _router(4)
    before = r.pick(session_id="sess-x")[0]
    r.replicas = r.replicas[:2]             # detach (drain/preempt)
    r.inflight = {0: 0, 1: 0}
    after = r.pick(session_id="sess-x")[0]
    assert after in (0, 1)
    # deterministic on the new set
    assert r.pick(session_id="sess-x")[0] == after
    assert before in range(4)


# --------------------------------------------------------------------------
# cluster tier (Python >= 3.12): coalesced exactly-once under chaos
# --------------------------------------------------------------------------

def _tiny_llm_config():
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=512, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)


def test_coalesced_stream_exactly_once_under_preempt_chaos(ray_start):
    """PR 9's preempt_one() against PR 10's coalesced streams: a replica
    preempted (and a second one hard-killed) mid-coalesced-chunk must
    deliver every token exactly once, as per-token iteration, matching
    the greedy oracle — the resume path carries token-granular state
    through the chunk envelope."""
    from ray_tpu import serve
    from ray_tpu.inference import LLMDeployment
    from ray_tpu.util.chaos import ServeReplicaKiller
    dep = serve.deployment(LLMDeployment, num_replicas=2,
                           preempt_grace_s=30.0)
    serve.run(dep.bind(_tiny_llm_config(), n_slots=2, max_len=512,
                       prefill_chunk=8, prefill_budget=16,
                       stream_coalesce_tokens=4, stream_coalesce_ms=10.0),
              name="llm-coalesce")
    h = serve.get_app_handle("llm-coalesce")
    oracle = list(h.options(stream=True).remote([5, 6, 7],
                                                max_new_tokens=32))
    assert len(oracle) == 32                # DRG unpacks to tokens
    killer = ServeReplicaKiller("llm-coalesce", "LLMDeployment")

    # graceful preemption mid-stream: drained replica finishes it
    gen = h.options(stream=True).remote([5, 6, 7], max_new_tokens=32)
    got = [next(gen) for _ in range(5)]
    assert killer.preempt_one()
    got.extend(gen)
    assert got == oracle
    assert killer.wait_for_replacement(timeout_s=90, handle=h)

    # hard kill mid-stream: resume_tokens continuation on the survivor
    gen = h.options(stream=True).remote([5, 6, 7], max_new_tokens=32)
    got = [next(gen) for _ in range(5)]     # > one coalesced chunk
    assert killer.kill_one(prefer_busy=True)
    got.extend(gen)
    assert got == oracle
    serve.delete("llm-coalesce")
