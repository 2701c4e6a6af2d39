"""Sharded serving plane (ray_tpu/serve/sharded.py + kv_quant.py):
mesh-gang replicas with int8 KV.

CPU unit tier (tier-1, any interpreter):
- int8 KV: quantize/dequantize round-trip tolerance, jnp/numpy mirror
  bit-identity, and prefix-cache HIT vs MISS greedy parity with the
  quantized block pool
- compile-once with quantization ON: decode_compile_count == 1 across
  requests of different lengths
- gang plumbing without a cluster: token digests, resume_tokens
  exactly-once, streaming protocol, GangRankKiller arming + the
  would-be SIGKILL (os.kill patched), ShellPool.checkout_many
  atomicity, digest-divergence wedging

The cluster tier (real gang attach over a Serve app, rank death
mid-decode, whole-gang drain -> shell revival -> exactly-once stream
resume) is 3.12-gated like every other cluster suite."""

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


def _replica(model, params, **kw):
    from ray_tpu.serve.sharded import ShardedEngineReplica
    base = dict(n_slots=2, max_len=64, prefill_chunk=4, prefill_budget=8,
                params_fn=lambda: params, seed=0)
    base.update(kw)
    return ShardedEngineReplica(model, **base)


PROMPT = [3, 1, 4, 1, 5, 9, 2, 6]


# ==========================================================================
# int8 KV quantization
# ==========================================================================

def test_int8_kv_roundtrip_tolerance_and_host_mirror(jax_cpu):
    import jax.numpy as jnp

    from ray_tpu.inference.kv_quant import (dequantize_kv, dequantize_kv_np,
                                            quantize_kv)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 16, 4, 8)).astype(np.float32)
    q, s = quantize_kv(jnp.asarray(x))
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    back = np.asarray(dequantize_kv(q, s, jnp.float32))
    # symmetric per-row int8: error bounded by half a quant step
    amax = np.abs(x).max(axis=-1, keepdims=True)
    assert np.all(np.abs(back - x) <= amax / 127 * 0.5 + 1e-7)
    # the host mirror of dequantize is bit-identical to the jnp path
    # (an int8 span landing in an fp store is dequantized on the host;
    # the other direction is quantized on the device: test_kv_cache.py)
    np.testing.assert_array_equal(
        back, dequantize_kv_np(np.asarray(q), np.asarray(s)))
    # all-zero rows must not divide by zero
    qz, sz = quantize_kv(jnp.zeros((1, 4, 2, 8), jnp.float32))
    assert np.all(np.asarray(qz) == 0) and np.all(np.asarray(sz) == 1.0)


def test_int8_slot_gain_formula():
    from ray_tpu.inference.kv_quant import slot_gain
    assert slot_gain(8, 2) == pytest.approx(2 * 8 / (8 + 4))
    assert slot_gain(128, 2) == pytest.approx(2 * 128 / 132)
    assert slot_gain(128, 4) > slot_gain(128, 2)   # fp32 baseline gains more


def test_int8_prefix_hit_greedy_parity(tiny):
    """The ISSUE gate: greedy output from an int8 prefix-cache HIT is
    bit-identical to the MISS that populated it (write-through
    quantize-and-reload on the miss path)."""
    _, model, params = tiny
    rep = _replica(model, params, kv_quant="int8", prefix_cache_slots=2)
    prompt = list(range(2, 26))             # 24 tokens = 6 full chunks
    miss = rep.generate(prompt, max_new_tokens=16)
    st0 = rep.stats()
    hit = rep.generate(prompt, max_new_tokens=16)
    st1 = rep.stats()
    assert hit == miss
    assert st1["prefix_tokens_saved"] > st0["prefix_tokens_saved"]
    assert st1["prefix_hits"] > st0["prefix_hits"]
    assert st1["kv_quant_slot_gain_vs_fp16"] > 1.0


# ==========================================================================
# compile-once with int8 blocks
# ==========================================================================

def test_compile_once_with_int8_blocks(tiny):
    _, model, params = tiny
    rep = _replica(model, params, kv_quant="int8", prefix_cache_slots=2)
    # the reference has the same block format: int8 blocks are not
    # greedy-exact against fp ones (a miss attends its finished chunks as
    # the store gives them back, so that a hit is bit-identical to it;
    # against a plain fp replica the 8-token prompt's first chunk alone
    # moves token 4 of this random-weight model)
    base = _replica(model, params, kv_quant="int8", prefix_cache_slots=2)
    for prompt, n in [(PROMPT, 20), (list(range(30)), 12), ([5], 24)]:
        assert rep.generate(prompt, max_new_tokens=n) == \
            base.generate(prompt, max_new_tokens=n)
    st = rep.stats()
    # one decode program across three request shapes
    assert st["decode_compile_count"] == 1
    assert st["requests_served"] == 3


# ==========================================================================
# gang plumbing: digests, resume, streaming, chaos, shell pool
# ==========================================================================

def test_stream_digest_deterministic_across_replicas(tiny):
    """Digest agreement raw material: two same-seed replicas produce
    the same (stream_seq, blake2b) pair per stream; a different stream
    bumps the sequence and changes the digest."""
    _, model, params = tiny
    a = _replica(model, params)
    b = _replica(model, params)
    assert a.last_stream_digest() is None
    a.generate(PROMPT, max_new_tokens=12)
    b.generate(PROMPT, max_new_tokens=12)
    da, db = a.last_stream_digest(), b.last_stream_digest()
    assert da == db and da[0] == 1 and len(da[1]) == 32
    a.generate([9, 9], max_new_tokens=4)
    assert a.last_stream_digest()[0] == 2
    assert a.last_stream_digest()[1] != da[1]


def test_digest_divergence_wedges_gang(tiny):
    """ReplicaShard wedges the whole gang when any peer's stream digest
    disagrees with rank 0's — split-brain SPMD output is never served."""
    from ray_tpu.serve.sharded_replica import ReplicaShard
    _, model, params = tiny
    shard = ReplicaShard.__new__(ReplicaShard)
    shard._callable = _replica(model, params)
    shard._callable.generate(PROMPT, max_new_tokens=8)
    shard._wedged = False
    local = shard._callable.last_stream_digest()

    class _Ref:
        def __init__(self, v):
            self.v = v

    class _PeerMethod:
        def __init__(self, v):
            self.v = v

        def remote(self, *a, **k):
            return _Ref(self.v)

    class _Peer:
        def __init__(self, v):
            self.run_shard = _PeerMethod(v)

    import ray_tpu
    orig = ray_tpu.get
    ray_tpu.get = lambda refs, timeout=None: [r.v for r in refs]
    try:
        shard._peers = [_Peer(local)]
        shard._verify_stream_digest()        # agreement: no-op
        assert not shard._wedged
        shard._peers = [_Peer((local[0], "0" * 32))]
        with pytest.raises(RuntimeError, match="digest divergence"):
            shard._verify_stream_digest()
        assert shard._wedged
    finally:
        ray_tpu.get = orig


def test_resume_tokens_exactly_once(tiny):
    """Severed-stream re-route: delivered tokens ride the prompt, the
    continuation is the bit-identical greedy suffix, nothing repeats."""
    _, model, params = tiny
    rep = _replica(model, params)
    out = rep.generate(PROMPT, max_new_tokens=24)
    res = rep.generate(PROMPT, max_new_tokens=24, resume_tokens=out[:10])
    assert res == out[10:]
    # fully-delivered stream: nothing left to emit
    assert rep.generate(PROMPT, max_new_tokens=24, resume_tokens=out) == []


def test_streaming_protocol_eager_first_chunk(tiny):
    _, model, params = tiny
    rep = _replica(model, params, stream_coalesce_tokens=8)
    chunks = list(rep(PROMPT, max_new_tokens=9))
    assert chunks[0] == [chunks[0][0]]      # TTFT: first token alone
    assert sum(len(c) for c in chunks) == 9
    assert [t for c in chunks for t in c] == rep.generate(
        PROMPT, max_new_tokens=9)


def test_gang_rank_killer_spec_env_and_rank0_immunity(tiny, monkeypatch):
    from ray_tpu.util.chaos import GangRankKiller
    killer = GangRankKiller(probability=1.0)
    assert killer.spec() == "gang_rank=1.0"
    env = killer.env({"A": "1", killer.SPEC_ENV: "shell_attach=0.5"})
    assert env[killer.SPEC_ENV] == "shell_attach=0.5,gang_rank=1.0"
    with pytest.raises(ValueError):
        GangRankKiller(probability=0.0)

    _, model, params = tiny
    rep = _replica(model, params)
    kills = []
    monkeypatch.setattr("os.kill", lambda pid, sig: kills.append((pid, sig)))
    killer.arm_local()
    try:
        # rank 0 never checks the hook: admission must survive chaos
        assert rep._rank == 0
        assert len(rep.generate(PROMPT, max_new_tokens=4)) == 4
        assert kills == []
        # a non-zero rank dies on its first step
        rep._rank = 1
        rep.generate(PROMPT, max_new_tokens=4)
        assert len(kills) >= 1
        import signal as _signal
        assert kills[0][1] == _signal.SIGKILL
    finally:
        rep._rank = 0
        GangRankKiller.disarm_local()


def test_shell_pool_checkout_many_is_atomic():
    from ray_tpu.serve.fleet import ShellPool

    class _Shell:
        pass

    pool = ShellPool(_Shell, size=4)
    pool.ensure()
    assert pool.idle() == 4
    assert pool.checkout_many(8) is None     # n or none: no partial gang
    assert pool.idle() == 4
    gang = pool.checkout_many(3)
    assert len(gang) == 3 and pool.idle() == 1
    assert pool.checkout_many(2) is None     # 1 idle < 2: untouched
    assert pool.idle() == 1
    assert pool.stats()["checked_out_total"] == 3


def test_drain_covers_whole_gang(tiny):
    """rank 0 owns admission, so begin_drain() on the replica drains
    the gang: the engine stops admitting and pending counts expose the
    drain progress the preemption lifecycle polls."""
    _, model, params = tiny
    rep = _replica(model, params)
    rep.generate(PROMPT, max_new_tokens=4)
    rep.begin_drain()
    st = rep.drain_status()
    assert st["draining"] and st["pending"] == 0
    with pytest.raises(RuntimeError):
        rep.generate(PROMPT, max_new_tokens=4)


def test_build_sharded_app_shape(tiny):
    from ray_tpu.serve.sharded import build_sharded_app
    app = build_sharded_app("llama-debug", num_hosts=2,
                            name="sharded-llm", n_slots=2)
    assert app.deployment.config.num_hosts == 2
    assert app.deployment.name == "sharded-llm"
    assert app.kwargs["n_slots"] == 2


# ==========================================================================
# cluster tier: real gang attach + rank-death recovery (3.12-gated)
# ==========================================================================

RAY_START = dict(num_cpus=8)


def test_gang_attach_and_rank_death_recovery(ray_start):
    """Acceptance: a 2-host sharded deployment serves greedy streams;
    GangRankKiller SIGKILLs rank 1 mid-decode; the gang wedges, drains
    whole, revives (pre-warmed shells or cold build) and the re-routed
    stream with resume_tokens continues bit-identically."""
    from ray_tpu import serve
    from ray_tpu.serve.sharded import build_sharded_app
    from ray_tpu.util.chaos import GangRankKiller

    app = build_sharded_app(
        "llama-debug", num_hosts=2, name="sharded-acc",
        n_slots=2, max_len=64, prefill_chunk=4, prefill_budget=8)
    handle = serve.run(app, name="sharded-acc")
    try:
        ref = handle.generate.remote(PROMPT, max_new_tokens=24)
        full = ref.result(timeout=120)
        assert len(full) == 24

        killer = GangRankKiller(probability=1.0)
        import os
        os.environ[killer.SPEC_ENV] = killer.spec()
        try:
            got, err = [], None
            try:
                for chunk in handle.options(stream=True).remote(
                        PROMPT, max_new_tokens=24):
                    got.extend(chunk)
            except Exception as e:          # rank death severs the stream
                err = e
            # whichever way the race lands, what arrived is a greedy
            # prefix delivered at most once
            assert full[:len(got)] == got
        finally:
            os.environ.pop(killer.SPEC_ENV, None)

        # recovery: the controller retires the wedged gang and revives;
        # the resumed request returns exactly the missing suffix
        deadline = time.monotonic() + 180
        res = None
        while time.monotonic() < deadline:
            try:
                res = handle.generate.remote(
                    PROMPT, max_new_tokens=24,
                    resume_tokens=got).result(timeout=60)
                break
            except Exception:
                time.sleep(2)
        assert res == full[len(got):]
    finally:
        serve.delete("sharded-acc")
