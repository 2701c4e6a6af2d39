"""Disaggregated prefill/decode serving plane (ray_tpu/serve/disagg.py
+ engine/scheduler/router/GCS extensions, ROADMAP item 1):

- chunk fingerprints + trie summaries (the cluster-routing currency)
- scheduler remote-prefill hold state
- GCS prefix_summaries publish / read / TTL-expire semantics
- router cluster longest-match vs session-hash tie-breaking
- KV payload framing round-trip
- engine KV export/import parity: greedy output bit-identical between
  remote-prefill and local-prefill paths, compile-once preserved
- deployment-level hand-off + every rung of the fallback ladder
  (including the PrefillExportKiller chaos spec)
- idle-span spill eligibility (ROADMAP item 4 leftover)

The first part is cluster-free; the cluster tier (full Serve app,
cross-replica route, prefill replica killed mid-export) follows it."""

import time

import numpy as np
import pytest


# --------------------------------------------------------------------------
# fingerprints + trie summary (pure host code)
# --------------------------------------------------------------------------

def test_chunk_fingerprints_rolling_and_divergence():
    from ray_tpu.inference.prefix_cache import chunk_fingerprints
    toks = list(range(100, 117))            # 17 tokens, chunk 4
    fps = chunk_fingerprints(toks, 4)
    assert len(fps) == 4                    # full chunks only
    # deterministic and prefix-stable: a longer prompt sharing the
    # prefix produces the same leading fingerprints
    fps2 = chunk_fingerprints(toks + [1, 2, 3, 4, 5], 4)
    assert fps2[:4] == fps
    # divergence at chunk i changes fingerprints from i on
    other = list(toks)
    other[5] = 999                          # inside chunk 1
    fps3 = chunk_fingerprints(other, 4)
    assert fps3[0] == fps[0]
    assert fps3[1] != fps[1] and fps3[2] != fps[2]
    # admission-cap plumbing
    assert chunk_fingerprints(toks, 4, max_chunks=2) == fps[:2]
    assert chunk_fingerprints([1, 2], 4) == []


def test_trie_summary_matches_chunk_fingerprints_and_caps_topk():
    from ray_tpu.inference import RadixPrefixCache
    from ray_tpu.inference.prefix_cache import chunk_fingerprints
    c = RadixPrefixCache(4, 8)
    toks = list(range(40, 52))              # 3 chunks
    c.insert(toks)
    s = c.summary()
    assert s["chunk"] == 4 and s["blocks"] == 3
    # the summary's fingerprints ARE the prompt's path fingerprints —
    # the router-side computation matches without seeing any tokens
    assert set(s["fps"]) == set(chunk_fingerprints(toks, 4))
    # top-k keeps the most recently touched nodes
    c.insert([7, 7, 7, 7])
    m, nodes = c.match(toks + [99])         # touch the whole chain
    assert m == 12
    c.release(nodes)
    top = c.summary(top_k=3)["fps"]
    assert len(top) == 3
    assert set(top) == set(chunk_fingerprints(toks, 4))


def test_peek_and_walk_semantics():
    from ray_tpu.inference import RadixPrefixCache
    c = RadixPrefixCache(4, 8)
    toks = list(range(10, 23))              # 13 tokens = 3 full chunks
    c.insert(toks)
    lookups0, hits0 = c.lookups, c.hits
    # peek: capped like match, but no pins, no stats
    assert c.peek(toks) == 12
    assert c.peek(toks[:12]) == 8           # cap leaves the last token
    assert c.peek([99] + toks[1:]) == 0
    assert (c.lookups, c.hits) == (lookups0, hits0)
    root = c._root
    assert all(n.pins == 0 for n in root.children.values())
    # walk: uncapped up to n_chunks, PINNED, still stats-free
    nodes = c.walk(toks, 3)
    assert len(nodes) == 3
    assert all(n.pins == 1 for n in nodes)
    assert (c.lookups, c.hits) == (lookups0, hits0)
    c.release(nodes)
    assert all(n.pins == 0 for n in nodes)
    assert c.walk(toks, 2) and len(c.walk(toks, 0)) == 0


# --------------------------------------------------------------------------
# scheduler: remote-prefill hold state
# --------------------------------------------------------------------------

def _sched(n_slots=2, budget=8):
    from ray_tpu.inference import Scheduler
    return Scheduler(n_slots, budget, chunk_size=4)


def test_hold_blocks_admission_until_release():
    from ray_tpu.inference import Request
    s = _sched()
    held = s.submit(Request(tokens=np.arange(6)), hold=True)
    assert s.plan_prefill() == []           # held: not admissible
    assert not s.has_work()                 # and not spinning the loop
    assert s.release_hold(held.rid)
    assert s.has_work()
    chunks = s.plan_prefill()
    assert chunks and chunks[0].state.rid == held.rid


def test_held_request_keeps_fifo_position_but_yields_slots():
    from ray_tpu.inference import Request
    s = _sched(n_slots=1)
    held = s.submit(Request(tokens=np.arange(4)), hold=True)
    other = s.submit(Request(tokens=np.arange(4)))
    # a later arrival admits past the held head (its KV is in flight)
    chunks = s.plan_prefill()
    assert [c.state.rid for c in chunks] == [other.rid]
    # the held request is still queued, in place, and admits on release
    s.release_hold(held.rid)
    assert s._queue[0].rid == held.rid


def test_held_request_still_reaped_on_cancel_and_release_is_idempotent():
    from ray_tpu.inference import Request
    s = _sched()
    held = s.submit(Request(tokens=np.arange(4)), hold=True)
    held.cancel()
    reaped = s.reap()
    assert [st.rid for st in reaped] == [held.rid]
    s.deliver()
    assert held.finish_reason == "cancelled"
    assert s.release_hold(held.rid) is False   # already gone


# --------------------------------------------------------------------------
# GCS prefix_summaries table: publish / read / expire
# --------------------------------------------------------------------------

def test_gcs_prefix_summary_publish_read_filter_and_expire():
    from ray_tpu._private.config import cfg
    from ray_tpu._private.gcs import GcsServer
    g = GcsServer()
    assert g.h_publish_prefix_summary(None, "rep-a", [1, 2, 3], 4,
                                      blocks=3, deployment="llm")
    g.h_publish_prefix_summary(None, "rep-b", [9], 4, deployment="other")
    rows = g.h_get_prefix_summaries(None)
    assert {r["replica_id"] for r in rows} == {"rep-a", "rep-b"}
    # id + deployment filters
    assert [r["replica_id"] for r in
            g.h_get_prefix_summaries(None, ids=["rep-a"])] == ["rep-a"]
    assert [r["replica_id"] for r in
            g.h_get_prefix_summaries(None, deployment="other")] == ["rep-b"]
    # last write wins per replica
    g.h_publish_prefix_summary(None, "rep-a", [5], 4)
    (row,) = g.h_get_prefix_summaries(None, ids=["rep-a"])
    assert row["fps"] == [5]
    # fps are bounded by the top-k knob
    big = list(range(cfg.prefix_summary_top_k + 50))
    g.h_publish_prefix_summary(None, "rep-c", big, 4)
    (row,) = g.h_get_prefix_summaries(None, ids=["rep-c"])
    assert len(row["fps"]) == cfg.prefix_summary_top_k
    # expiry: rows older than the TTL vanish at read time (a dead
    # replica stops attracting routes without explicit teardown)
    g.prefix_summaries["rep-a"]["ts"] -= cfg.prefix_summary_ttl_s + 1
    assert "rep-a" not in {r["replica_id"]
                           for r in g.h_get_prefix_summaries(None)}
    assert "rep-a" not in g.prefix_summaries
    # empty/garbage publishes are refused
    assert g.h_publish_prefix_summary(None, "", [1], 4) is False


# --------------------------------------------------------------------------
# router: cluster longest-match vs session-hash tie-breaking
# --------------------------------------------------------------------------

def _router(n, chunk=4):
    import threading

    from ray_tpu.serve.handle import _Router
    r = _Router.__new__(_Router)     # skip ctor (no long-poll client)
    r.deployment_name = "d"
    r.app_name = "a"
    r.replicas = [object() for _ in range(n)]
    r.inflight = {i: 0 for i in range(n)}
    r.shared_load = {}
    r.version = 0
    r.resumable = False
    r.coalesced = False
    r.prefix_routed = True
    r.replica_ids = [f"rep-{i}" for i in range(n)]
    r._summaries = {}
    r._summary_chunk = chunk
    r._last_summary_refresh = time.monotonic() + 1e6   # never re-pull
    r.lock = threading.Lock()
    r._last_refresh = time.monotonic() + 1e6           # never refresh
    r.model_map = {}
    return r


def _set_summary(r, idx, tokens, depth, chunk=4):
    from ray_tpu.inference.prefix_cache import chunk_fingerprints
    r._summaries[f"rep-{idx}"] = set(
        chunk_fingerprints(tokens, chunk, max_chunks=depth))


def test_router_routes_to_deepest_cluster_match():
    prompt = list(range(60, 77))            # 4 full chunks of 4
    r = _router(4)
    _set_summary(r, 1, prompt, depth=1)
    _set_summary(r, 3, prompt, depth=3)
    # deepest match wins regardless of load or session hash
    r.inflight = {0: 0, 1: 0, 2: 0, 3: 99}
    for s in ("sess-a", "sess-b", ""):
        idx, _ = r.pick(session_id=s, prompt_tokens=prompt)
        assert idx == 3
        r._dec(idx)


def test_router_tie_breaks_to_session_then_least_loaded():
    import zlib
    prompt = list(range(10, 27))
    r = _router(4)
    _set_summary(r, 0, prompt, depth=2)
    _set_summary(r, 2, prompt, depth=2)
    # session whose sticky replica is among the deepest: sticky wins
    sticky2 = next(s for s in (f"s{i}" for i in range(64))
                   if zlib.crc32(str(s).encode()) % 4 == 2)
    idx, _ = r.pick(session_id=sticky2, prompt_tokens=prompt)
    assert idx == 2
    r._dec(idx)
    # session hashing OUTSIDE the winner set: least-loaded winner
    sticky1 = next(s for s in (f"s{i}" for i in range(64))
                   if zlib.crc32(str(s).encode()) % 4 == 1)
    r.inflight = {0: 5, 1: 0, 2: 0, 3: 0}
    idx, _ = r.pick(session_id=sticky1, prompt_tokens=prompt)
    assert idx == 2
    r._dec(idx)
    # no session: least-loaded winner
    r.inflight = {0: 0, 1: 0, 2: 7, 3: 0}
    idx, _ = r.pick(prompt_tokens=prompt)
    assert idx == 0


def test_router_falls_back_to_session_hash_without_match():
    prompt = list(range(30, 47))
    r = _router(4)
    # summaries exist but cover a DIFFERENT prefix -> session rung
    _set_summary(r, 1, list(range(200, 217)), depth=3)
    picks = {r.pick(session_id="sess-x", prompt_tokens=prompt)[0]
             for _ in range(6)}
    assert len(picks) == 1                  # sticky, not prefix-routed
    # avoided deepest replica falls back too
    r2 = _router(2)
    _set_summary(r2, 0, prompt, depth=2)
    idx, _ = r2.pick(prompt_tokens=prompt, avoid={0})
    assert idx == 1


def test_router_short_prompt_and_disabled_flag_skip_prefix_rung():
    r = _router(3)
    _set_summary(r, 1, list(range(8)), depth=2)
    # sub-chunk prompt: no fingerprints, session rung decides
    idx, _ = r.pick(session_id="s", prompt_tokens=[1, 2])
    assert idx in range(3)
    r._dec(idx)
    r.prefix_routed = False
    idx2, _ = r.pick(session_id="s", prompt_tokens=list(range(8)))
    assert idx2 == idx                      # same session-hash pick


# --------------------------------------------------------------------------
# KV payload framing
# --------------------------------------------------------------------------

def test_pack_unpack_roundtrip_and_zero_copy_views():
    from ray_tpu.serve.disagg import pack_kv_spans, unpack_kv_spans
    rng = np.random.RandomState(3)
    shape = (2, 1, 4, 2, 8)                 # [n_layers, 1, C, Hkv, D]
    spans = [(rng.randn(*shape).astype(np.float32),
              rng.randn(*shape).astype(np.float32)) for _ in range(3)]
    buf = pack_kv_spans(spans)
    out = unpack_kv_spans(buf)
    assert len(out) == 3
    for (k, v), (k2, v2) in zip(spans, out):
        assert np.array_equal(k, k2) and np.array_equal(v, v2)
    # memoryview input (the arena view ray_tpu.get hands back) works and
    # the arrays are views into it, not copies
    out2 = unpack_kv_spans(memoryview(buf))
    assert not out2[0][0].flags.owndata
    assert np.array_equal(out2[2][1], spans[2][1])
    assert unpack_kv_spans(pack_kv_spans([])) == []


# --------------------------------------------------------------------------
# engine: export/import parity + compile-once
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
    cfg = dict(n_slots=2, max_len=48, prefill_chunk=4, prefill_budget=8,
               prefix_cache_slots=1)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg))


def _drain(eng, handle, max_steps=300):
    for _ in range(max_steps):
        eng.step()
        if handle.finish_reason is not None:
            return handle.tokens()
    raise AssertionError("request did not finish")


def test_remote_prefill_greedy_bit_identical_and_compile_once(tiny):
    """The acceptance contract: a prompt prefilled on ANOTHER engine,
    shipped as packed KV spans and imported, produces greedy output
    bit-identical to the colocated path — with decode_compile_count
    still 1 on the importing engine."""
    from ray_tpu.serve.disagg import pack_kv_spans, unpack_kv_spans
    _, model, params = tiny
    rng = np.random.RandomState(11)
    prompt = rng.randint(0, 128, 17)
    # colocated oracle
    eng_co = _engine(model, params)
    want = _drain(eng_co, eng_co.submit(prompt, max_new_tokens=10))

    prefill = _engine(model, params, prefix_cache_slots=2)
    _drain(prefill, prefill.submit(prompt, max_new_tokens=1))
    covered, spans = prefill.export_kv_blocks(prompt)
    assert covered == 16 and len(spans) == 4
    assert prefill.kv_exports == 1

    decode = _engine(model, params, prefix_cache_slots=2)
    payload = pack_kv_spans(spans)          # the real wire framing
    imported = decode.import_kv_blocks(prompt[:covered],
                                       unpack_kv_spans(payload))
    assert imported == 16 and decode.kv_imports == 1
    h = decode.submit(prompt, max_new_tokens=10)
    got = _drain(decode, h)
    assert h.prefix_matched == 16           # admission skipped prefill
    assert got == want                      # bit-identical
    st = decode.stats()
    assert st["decode_compile_count"] == 1
    assert st["remote_prefix_tokens"] == 16
    assert decode._blocks._import_fn._cache_size() == 1
    assert prefill._blocks._export_fn._cache_size() == 1
    # a redundant import of already-cached chunks is a no-op
    assert decode.import_kv_blocks(prompt[:covered],
                                   unpack_kv_spans(payload)) == 0


def test_import_partial_prefix_and_longer_prompt_reuse(tiny):
    """An imported prefix serves LONGER prompts sharing it (cluster
    cache semantics), and a partial import still shortens prefill."""
    from ray_tpu.serve.disagg import pack_kv_spans, unpack_kv_spans
    _, model, params = tiny
    rng = np.random.RandomState(12)
    shared = rng.randint(0, 128, 12)        # 3 full chunks
    prefill = _engine(model, params, prefix_cache_slots=2)
    _drain(prefill, prefill.submit(shared, max_new_tokens=1))
    covered, spans = prefill.export_kv_blocks(shared, max_chunks=3)
    assert covered == 12
    decode = _engine(model, params, prefix_cache_slots=2)
    decode.import_kv_blocks(shared, unpack_kv_spans(
        pack_kv_spans(spans)))
    longer = np.concatenate([shared, rng.randint(0, 128, 7)])
    eng_co = _engine(model, params)
    want = _drain(eng_co, eng_co.submit(longer, max_new_tokens=8))
    h = decode.submit(longer, max_new_tokens=8)
    assert _drain(decode, h) == want
    assert h.prefix_matched == 12
    assert decode.decode_compile_count == 1


# --------------------------------------------------------------------------
# deployment tier: hand-off + fallback ladder
# --------------------------------------------------------------------------

def _mk_prefill(tiny_fixture, **kw):
    from ray_tpu.serve.disagg import PrefillLLMDeployment
    cfg, _model, params = tiny_fixture
    args = dict(n_slots=2, max_len=64, prefill_chunk=4, prefill_budget=8,
                prefix_cache_slots=2, params_fn=lambda: params)
    args.update(kw)
    return PrefillLLMDeployment(cfg, **args)


def _mk_decode(tiny_fixture, prefill, **kw):
    from ray_tpu.serve.disagg import DisaggLLMDeployment
    cfg, _model, params = tiny_fixture
    args = dict(n_slots=2, max_len=64, prefill_chunk=4, prefill_budget=8,
                prefix_cache_slots=2, params_fn=lambda: params,
                prefill=prefill)
    args.update(kw)
    return DisaggLLMDeployment(cfg, **args)


def test_disagg_deployment_handoff_end_to_end(tiny):
    from ray_tpu.inference import LLMDeployment
    cfg, _model, params = tiny
    oracle_dep = LLMDeployment(cfg, n_slots=2, max_len=64,
                               prefill_chunk=4, prefill_budget=8,
                               prefix_cache_slots=0,
                               params_fn=lambda: params)
    prefill = _mk_prefill(tiny)
    decode = _mk_decode(tiny, prefill)
    try:
        prompt = list(range(50, 67))        # 17 tokens: 4 full chunks
        want = oracle_dep.generate(prompt, max_new_tokens=10)
        got = decode.generate(prompt, max_new_tokens=10)
        assert got == want
        assert prefill.engine.kv_exports >= 1
        assert decode.engine.kv_imports == 1
        assert decode.engine.remote_prefix_tokens == 16
        assert decode.engine.decode_compile_count == 1
        # second request: local hit, no new hand-off
        assert decode.generate(prompt, max_new_tokens=10) == want
        assert decode.engine.kv_imports == 1
        # hold fully released: nothing parked in the queue
        assert decode.engine.sched.queue_depth() == 0
    finally:
        oracle_dep.engine.stop()
        prefill.engine.stop()
        decode.engine.stop()


class _BrokenPrefill:
    def prefill_export(self, tokens):
        raise RuntimeError("prefill tier unreachable")


def test_disagg_falls_back_to_local_prefill_on_handoff_failure(tiny):
    from ray_tpu.inference import LLMDeployment
    cfg, _model, params = tiny
    oracle_dep = LLMDeployment(cfg, n_slots=2, max_len=64,
                               prefill_chunk=4, prefill_budget=8,
                               prefix_cache_slots=0,
                               params_fn=lambda: params)
    decode = _mk_decode(tiny, _BrokenPrefill())
    try:
        prompt = list(range(20, 37))
        want = oracle_dep.generate(prompt, max_new_tokens=8)
        got = decode.generate(prompt, max_new_tokens=8)
        assert got == want                  # exactly-once, rung 4
        assert decode.engine.kv_imports == 0
        assert decode.engine.sched.queue_depth() == 0   # hold released
    finally:
        oracle_dep.engine.stop()
        decode.engine.stop()


def test_prefill_export_killer_spec_forces_fallback(tiny):
    """The chaos satellite: with RAY_TPU_TESTING_RPC_FAILURE=
    "prefill_export=1.0" armed, every export dies (entry or pre-return)
    and the decode tier must fall back to local prefill with identical
    output — the exception-shaped half of 'killed mid-export'."""
    from ray_tpu.inference import LLMDeployment
    from ray_tpu.util.chaos import PrefillExportKiller
    cfg, _model, params = tiny
    oracle_dep = LLMDeployment(cfg, n_slots=2, max_len=64,
                               prefill_chunk=4, prefill_budget=8,
                               prefix_cache_slots=0,
                               params_fn=lambda: params)
    prefill = _mk_prefill(tiny)
    decode = _mk_decode(tiny, prefill)
    killer = PrefillExportKiller(1.0)
    try:
        prompt = list(range(70, 87))
        want = oracle_dep.generate(prompt, max_new_tokens=8)
        killer.arm_local()
        with pytest.raises(Exception):
            prefill.prefill_export(prompt)  # the injection really fires
        got = decode.generate(prompt, max_new_tokens=8)
        assert got == want
        assert decode.engine.kv_imports == 0
    finally:
        killer.disarm_local()
        oracle_dep.engine.stop()
        prefill.engine.stop()
        decode.engine.stop()


def test_prefill_export_inline_payload_contract(tiny):
    """Outside a cluster prefill_export inlines the payload (no arena);
    the covered/chunk fields still line up with the admission cap."""
    prefill = _mk_prefill(tiny)
    try:
        prompt = list(range(90, 107))       # 17 tokens
        out = prefill.prefill_export(prompt)
        assert out["covered"] == 16 and out["chunk"] == 4
        assert "payload" in out and out.get("ref") is None
        from ray_tpu.serve.disagg import unpack_kv_spans
        assert len(unpack_kv_spans(out["payload"])) == 4
    finally:
        prefill.engine.stop()


def test_summary_publisher_noop_outside_cluster(tiny):
    """Direct instantiation (no runtime context): the publisher must
    not spawn a thread or publish anything."""
    prefill = _mk_prefill(tiny)
    try:
        pub = prefill._publisher
        assert pub._thread is None and pub.published == 0
    finally:
        prefill.engine.stop()


# --------------------------------------------------------------------------
# span spill eligibility (satellite: ROADMAP item 4 leftover)
# --------------------------------------------------------------------------

class _FakeSpanStore:
    """Duck-typed store for the node-manager span-spill sweep: spans
    with controllable age/pins/sealed state."""

    def __init__(self, spans, now=1000):
        self._spans = dict(spans)           # oid -> info dict
        self._now = now
        self.bytes_in_use = sum(s["data_size"] for s in spans.values())
        self.capacity = 100

    def list_spans(self):
        return list(self._spans)

    def object_info(self, oid):
        return self._spans.get(oid)

    def now_sec(self):
        return self._now

    def stats(self):
        return {"bytes_in_use": self.bytes_in_use,
                "capacity": self.capacity}


def _nm_with(store):
    # node_manager pulls in the native store at import time -> 3.12 only
    from ray_tpu._private.node_manager import NodeManager
    nm = NodeManager.__new__(NodeManager)
    nm.store = store
    nm.spilled = {}
    spilled = []

    def spill_one(oid, _os):
        info = store._spans.pop(oid, None)
        if info is None:
            return None
        spilled.append(oid)
        store.bytes_in_use -= info["data_size"]
        return info["data_size"]

    nm._spill_one = spill_one
    return nm, spilled


def _span(size=10, age=100, pins=0, sealed=True, now=1000):
    return {"data_size": size, "meta_size": 0, "pins": pins,
            "stripe": 0, "ctime_sec": now - age, "is_span": True,
            "sealed": sealed, "flags": 0}


def test_idle_unpinned_spans_spill_oldest_first_until_target():
    store = _FakeSpanStore({
        b"old": _span(size=40, age=500),
        b"mid": _span(size=40, age=100),
        b"new": _span(size=40, age=1),      # younger than the idle gate
        b"pin": _span(size=40, age=500, pins=1),
        b"raw": _span(size=40, age=500, sealed=False),
    })
    nm, spilled = _nm_with(store)
    # target high enough that ONE span suffices: oldest goes, rest stay
    n, freed = nm._spill_idle_spans(None, target_bytes=180)
    assert spilled == [b"old"] and n == 1 and freed == 40
    # more pressure: the next eligible span goes; pinned/unsealed/young
    # never do
    n, freed = nm._spill_idle_spans(None, target_bytes=1)
    assert spilled == [b"old", b"mid"]
    assert set(store._spans) == {b"new", b"pin", b"raw"}


def test_span_spill_noop_without_spans_or_eligible_rows():
    store = _FakeSpanStore({})
    nm, spilled = _nm_with(store)
    assert nm._spill_idle_spans(None) == (0, 0)
    store2 = _FakeSpanStore({b"pin": _span(pins=2)})
    nm2, spilled2 = _nm_with(store2)
    assert nm2._spill_idle_spans(None) == (0, 0) and spilled2 == []


def test_list_spans_filters_spanning_objects():
    pytest.importorskip("ray_tpu._private.object_store")
    import tempfile

    from ray_tpu._private.object_store import ObjectStoreClient
    with tempfile.TemporaryDirectory() as d:
        store = ObjectStoreClient(path=f"{d}/arena", size=8 << 20,
                                  create=True, stripes=2)
        try:
            oid_a = bytes(range(20))
            buf = store.create(oid_a, 128)
            store.seal(oid_a)
            oid_s = bytes(range(1, 21))
            out = store.create_spanning(oid_s, 4096)
            store.seal(oid_s)
            assert store.list_spans() == [oid_s]
            assert oid_a in store.list_objects()
        finally:
            store.close()


# --------------------------------------------------------------------------
# cluster tier (Python >= 3.12): full Serve app, cross-replica routing,
# prefill replica killed mid-export
# --------------------------------------------------------------------------

def _tiny_llm_config():
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=512, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)


def test_disagg_serving_cross_replica_route_and_handoff(ray_start):
    """Acceptance: a request whose prefix was prefilled on a DIFFERENT
    replica is routed by cluster-wide longest match, skips local
    prefill via the KV hand-off, and yields greedy output bit-identical
    to the colocated path."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.disagg import build_disagg_app
    from ray_tpu._private.config import cfg
    app = build_disagg_app(
        _tiny_llm_config(), decode_replicas=2, prefill_replicas=1,
        prefill_kwargs=dict(max_len=256, prefill_chunk=8,
                            prefill_budget=32, prefix_cache_slots=4,
                            params_fn=None, seed=0),
        decode_kwargs=dict(n_slots=2, max_len=256, prefill_chunk=8,
                           prefill_budget=32, prefix_cache_slots=4,
                           seed=0))
    serve.run(app, name="llm-disagg")
    h = serve.get_app_handle("llm-disagg")
    prompt = list(range(3, 40))             # 37 tokens: 4 full chunks
    # oracle from a colocated deployment with identical seed/params
    from ray_tpu.inference import LLMDeployment
    co = serve.deployment(LLMDeployment, name="co")
    serve.run(co.bind(_tiny_llm_config(), n_slots=2, max_len=256,
                      prefill_chunk=8, prefill_budget=32, seed=0),
              name="llm-co")
    oracle = list(serve.get_app_handle("llm-co").options(
        stream=True).remote(prompt, max_new_tokens=24))

    # first request (session A) warms exactly one decode replica
    got = list(h.options(stream=True, session_id="sess-A").remote(
        prompt, max_new_tokens=24))
    assert got == oracle
    # wait for that replica's summary to publish
    deadline = time.monotonic() + 3 * cfg.prefix_summary_interval_s + 5
    rows = []
    while time.monotonic() < deadline:
        rows = ray_tpu._get_worker().gcs_call("get_prefix_summaries")
        if any(r.get("fps") for r in rows):
            break
        time.sleep(0.5)
    assert any(r.get("fps") for r in rows), rows
    # a DIFFERENT session with the same prefix must route to the warmed
    # replica by cluster-wide longest match (session hash alone would
    # spread) and still produce the oracle output
    router = h._router
    router.refresh(force=True)
    router._last_summary_refresh = 0.0
    got2 = list(h.options(stream=True, session_id="sess-B").remote(
        prompt, max_new_tokens=24))
    assert got2 == oracle
    serve.delete("llm-co")
    serve.delete("llm-disagg")


def test_prefill_replica_killed_mid_export_falls_back(ray_start):
    """Chaos satellite: kill the prefill replica while the decode tier
    depends on it — every stream must still deliver exactly-once tokens
    matching the colocated oracle (fallback ladder rung 4)."""
    from ray_tpu import serve
    from ray_tpu.serve.disagg import build_disagg_app
    from ray_tpu.util.chaos import ServeReplicaKiller
    app = build_disagg_app(
        _tiny_llm_config(), decode_replicas=1, prefill_replicas=1,
        prefill_kwargs=dict(max_len=256, prefill_chunk=8,
                            prefill_budget=32, prefix_cache_slots=4,
                            seed=0),
        decode_kwargs=dict(n_slots=2, max_len=256, prefill_chunk=8,
                           prefill_budget=32, prefix_cache_slots=4,
                           seed=0, handoff_timeout_s=5.0))
    serve.run(app, name="llm-disagg-chaos")
    h = serve.get_app_handle("llm-disagg-chaos")
    prompt = list(range(5, 42))
    oracle = list(h.options(stream=True).remote(prompt,
                                                max_new_tokens=16))
    killer = ServeReplicaKiller("llm-disagg-chaos", "prefill")
    assert killer.kill_one()
    # the very next cold prompt finds the prefill tier dead mid-cycle:
    # the hand-off rung fails and local prefill serves it exactly-once
    prompt2 = list(range(50, 87))
    got = list(h.options(stream=True).remote(prompt2, max_new_tokens=16))
    assert len(got) == 16
    assert got == list(h.options(stream=True).remote(
        prompt2, max_new_tokens=16))
    # original prompt still exact after the chaos
    assert list(h.options(stream=True).remote(
        prompt, max_new_tokens=16)) == oracle
    serve.delete("llm-disagg-chaos")
