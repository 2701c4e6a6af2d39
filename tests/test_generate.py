"""KV-cache decode + autoregressive generation (the serving inference
engine; reference ships no model code — parity target is the decode
correctness contract every inference stack owes: cached stepwise logits
must equal the full causal forward).

Runs on the CPU (conftest): a chip's bf16 default matmuls would turn
these exactness checks into noise comparisons."""

import dataclasses
import functools

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
def debug_model(jax_cpu):
    import jax.numpy as jnp

    from ray_tpu.models import MODEL_REGISTRY, TransformerLM
    cfg = dataclasses.replace(MODEL_REGISTRY["llama-debug"],
                              dtype=jnp.float32, param_dtype=jnp.float32,
                              remat=False)
    model = TransformerLM(cfg)
    tokens = jax_cpu.random.randint(jax_cpu.random.PRNGKey(1), (2, 12), 0,
                                    cfg.vocab_size)
    params = model.init(jax_cpu.random.PRNGKey(0), tokens)["params"]
    return cfg, model, params, tokens


def test_cached_decode_matches_full_forward(jax_cpu, debug_model):
    """Prefill + single-token decode steps reproduce the full causal
    forward's logits at every position (scanned-layer layout)."""
    import jax.numpy as jnp

    from ray_tpu.models import init_cache
    cfg, model, params, tokens = debug_model
    full = model.apply({"params": params}, tokens)
    cache = init_cache(cfg, 2, 12, dtype=jnp.float32)
    lg, cache = model.apply({"params": params}, tokens[:, :8],
                            cache=cache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :8]),
                               rtol=2e-4, atol=2e-4)
    for t in range(8, 12):
        lg, cache = model.apply({"params": params}, tokens[:, t:t + 1],
                                cache=cache)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=2e-4, atol=2e-4)
    assert int(cache["idx"]) == 12


def test_cached_decode_matches_unrolled_layers(jax_cpu, debug_model):
    """Same contract on the scan_layers=False param layout."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerLM, init_cache
    cfg, _, _, tokens = debug_model
    cfg2 = dataclasses.replace(cfg, scan_layers=False)
    model = TransformerLM(cfg2)
    params = model.init(jax_cpu.random.PRNGKey(0), tokens)["params"]
    full = model.apply({"params": params}, tokens)
    cache = init_cache(cfg2, 2, 12, dtype=jnp.float32)
    _, cache = model.apply({"params": params}, tokens[:, :5], cache=cache)
    lg = None
    for t in range(5, 12):
        lg, cache = model.apply({"params": params}, tokens[:, t:t + 1],
                                cache=cache)
    np.testing.assert_allclose(np.asarray(lg[:, 0]),
                               np.asarray(full[:, 11]),
                               rtol=2e-4, atol=2e-4)


def test_chunked_prefill_matches_one_shot(jax_cpu, debug_model):
    """Prefill split into budgeted chunks through the cached-attention
    path (chunked_prefill=True, idx>0) reproduces the one-shot prefill
    logits at every position — the empty-cache restriction is lifted."""
    import jax.numpy as jnp

    from ray_tpu.models import init_cache
    cfg, model, params, tokens = debug_model
    full = model.apply({"params": params}, tokens)
    one_shot = init_cache(cfg, 2, 12, dtype=jnp.float32)
    lg_one, one_shot = model.apply({"params": params}, tokens,
                                   cache=one_shot)
    cache = init_cache(cfg, 2, 12, dtype=jnp.float32)
    lgs = []
    for lo, hi in [(0, 5), (5, 9), (9, 12)]:      # uneven chunks
        lg, cache = model.apply({"params": params}, tokens[:, lo:hi],
                                cache=cache, chunked_prefill=True)
        lgs.append(lg)
    chunked = jnp.concatenate(lgs, axis=1)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(lg_one),
                               rtol=2e-4, atol=2e-4)
    assert int(cache["idx"]) == 12
    # caches agree -> subsequent decode steps agree
    np.testing.assert_allclose(np.asarray(cache["k"]),
                               np.asarray(one_shot["k"]),
                               rtol=2e-4, atol=2e-4)


def test_per_slot_decode_positions(jax_cpu, debug_model):
    """cache['idx'] as a per-row vector: each row decodes at its own
    length (the slot-pool contract). Row parity against independent
    scalar-idx decodes at different lengths."""
    import jax.numpy as jnp

    from ray_tpu.models import init_cache
    cfg, model, params, tokens = debug_model
    lens = [7, 4]
    # reference: each row prefilled alone to its own length, one decode
    want = []
    for b, ln in enumerate(lens):
        c = init_cache(cfg, 1, 12, dtype=jnp.float32)
        _, c = model.apply({"params": params}, tokens[b:b + 1, :ln],
                           cache=c)
        lg, _ = model.apply({"params": params}, tokens[b:b + 1, ln:ln + 1],
                            cache=c)
        want.append(np.asarray(lg[0, 0]))
    # slot pool: both rows in one cache at different idx
    pool = {"k": jnp.zeros((cfg.n_layers, 2, 12, cfg.n_kv_heads,
                            cfg.head_dim), jnp.float32),
            "v": jnp.zeros((cfg.n_layers, 2, 12, cfg.n_kv_heads,
                            cfg.head_dim), jnp.float32),
            "idx": jnp.zeros((), jnp.int32)}
    for b, ln in enumerate(lens):
        c = init_cache(cfg, 1, 12, dtype=jnp.float32)
        _, c = model.apply({"params": params}, tokens[b:b + 1, :ln],
                           cache=c)
        pool["k"] = pool["k"].at[:, b:b + 1].set(c["k"])
        pool["v"] = pool["v"].at[:, b:b + 1].set(c["v"])
    pool["idx"] = jnp.asarray(lens, jnp.int32)
    step_tok = jnp.stack([tokens[b, ln] for b, ln in enumerate(lens)])
    lg, new = model.apply({"params": params}, step_tok[:, None],
                          cache=pool)
    for b in range(2):
        np.testing.assert_allclose(np.asarray(lg[b, 0]), want[b],
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(new["idx"]),
                                  np.asarray(lens) + 1)


def test_generate_greedy_matches_stepwise_argmax(jax_cpu, debug_model):
    """make_generate_fn's one-program generation equals a hand loop of
    full forwards + argmax."""
    from ray_tpu.models import make_generate_fn
    from ray_tpu.parallel import MeshConfig, make_mesh
    cfg, model, params, tokens = debug_model
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, seq=1, tensor=1),
                     devices=jax_cpu.devices()[:1])
    B, P, N = 2, 12, 6
    _, gen_fn, _ = make_generate_fn(model, mesh, batch=B, prompt_len=P,
                                    max_new_tokens=N)
    out = np.asarray(gen_fn(params, tokens, jax_cpu.random.PRNGKey(7)))
    # reference: repeated full forwards (no cache), greedy
    cur = np.asarray(tokens)
    want = []
    for _ in range(N):
        logits = model.apply({"params": params},
                             jax_cpu.numpy.asarray(cur))
        nxt = np.asarray(logits[:, -1, :]).argmax(-1)
        want.append(nxt)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, np.stack(want, axis=1))


def test_generate_sharded_mesh(jax_cpu):
    """Generation jitted over an fsdp x tensor mesh: sharded params +
    sharded KV cache, replicated output tokens, deterministic greedy."""
    from ray_tpu.models import MODEL_REGISTRY, TransformerLM, \
        make_generate_fn
    from ray_tpu.parallel import MeshConfig, make_mesh
    if len(jax_cpu.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = MODEL_REGISTRY["llama-debug"]
    model = TransformerLM(cfg)
    mesh = make_mesh(MeshConfig(data=1, fsdp=4, seq=1, tensor=2),
                     devices=jax_cpu.devices()[:8])
    B, P, N = 8, 16, 8
    init_fn, gen_fn, _ = make_generate_fn(model, mesh, batch=B,
                                          prompt_len=P, max_new_tokens=N)
    params = init_fn(jax_cpu.random.PRNGKey(0))
    prompt = jax_cpu.random.randint(jax_cpu.random.PRNGKey(1), (B, P), 0,
                                    cfg.vocab_size)
    out = np.asarray(gen_fn(params, prompt, jax_cpu.random.PRNGKey(2)))
    assert out.shape == (B, N)
    assert out.min() >= 0 and out.max() < cfg.vocab_size
    out2 = np.asarray(gen_fn(params, prompt, jax_cpu.random.PRNGKey(9)))
    np.testing.assert_array_equal(out, out2)     # greedy ignores rng
    _, gen_t, _ = make_generate_fn(model, mesh, batch=B, prompt_len=P,
                                   max_new_tokens=N, temperature=1.0)
    a = np.asarray(gen_t(params, prompt, jax_cpu.random.PRNGKey(3)))
    b = np.asarray(gen_t(params, prompt, jax_cpu.random.PRNGKey(4)))
    assert (a != b).any()


# ---- the cached forward against the uncached one, shape by shape --------
#
# One pool buffer passes through the cached forward: the layers read it
# and one write adds their new rows to it (models/transformer.py
# `_decode`, `_cache_write`). The cases below hold that path to the
# full causal forward (`cache=None`)
# at 1e-5 in float32, and to leaving every other position of the pool
# bit-identical, for every way a caller addresses the pool.

_PARITY_M = 12           # pool positions per row
_PARITY_MODES = {
    # name: (idx per row | scalar, new tokens L, chunked_prefill)
    # a row at 0, a row mid-way and a row at M-1, one token each: the
    # engine's decode step
    "rows_decode": ([0, 5, _PARITY_M - 1], 1, False),
    # per-row idx with L > 1: several new rows a slot, each slot at its
    # own length (no program of the repo calls the model so)
    "rows_verify": ([0, 3, _PARITY_M - 4], 4, True),
    # scalar idx, one token: make_generate_fn's decode step
    "scalar_decode": (6, 1, False),
    # scalar idx, L > 1 continuing an occupied cache: a prefill chunk
    "scalar_chunk": (5, 4, True),
}


@pytest.fixture(scope="module")
def parity_models(jax_cpu):
    """(scan_layers, n_experts) -> (cfg, model, params), built once."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, TransformerLM

    @functools.lru_cache(maxsize=None)
    def get(scan_layers, n_experts):
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=3, n_heads=4,
            n_kv_heads=2, d_ff=64, max_seq_len=32, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False,
            scan_layers=scan_layers, n_experts=n_experts, expert_top_k=2,
            # capacity = the group's length: no token is dropped, so a
            # row scored alone and in a batch route alike
            capacity_factor=n_experts / 2 if n_experts else 1.25)
        model = TransformerLM(cfg)
        params = model.init(jax_cpu.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        return cfg, model, params
    return get


@pytest.fixture(scope="module")
def parity_run(jax_cpu, parity_models):
    """One cached forward per case, shared by the two tests that read
    it: returns (logits, want, pool_before, pool_after, starts, L).
    Every case of a model shares one uncached forward and one noise
    pool, so that a case compiles only its own cached forward."""
    import jax.numpy as jnp
    B, M, LMAX = 3, _PARITY_M, 4

    @functools.lru_cache(maxsize=None)
    def base(scan_layers, n_experts):
        cfg, model, params = parity_models(scan_layers, n_experts)
        kt, kh, kk, kv = jax_cpu.random.split(jax_cpu.random.PRNGKey(3), 4)
        tokens = jax_cpu.random.randint(kt, (B, M + LMAX), 0,
                                        cfg.vocab_size)
        other = jax_cpu.random.randint(kh, (B, M), 0, cfg.vocab_size)
        shape = (cfg.n_layers, B, M, cfg.n_kv_heads, cfg.head_dim)
        noise = {"k": jax_cpu.random.normal(kk, shape, jnp.float32),
                 "v": jax_cpu.random.normal(kv, shape, jnp.float32)}
        full = model.apply({"params": params}, tokens)
        return tokens, other, noise, np.asarray(full)

    @functools.lru_cache(maxsize=None)
    def run(scan_layers, n_experts, mode):
        cfg, model, params = parity_models(scan_layers, n_experts)
        tokens, other, noise, full = base(scan_layers, n_experts)
        idx, L, chunked = _PARITY_MODES[mode]
        starts = np.asarray(idx if isinstance(idx, list) else [idx] * B)
        # every row's history in one forward over a pool of noise; from
        # a row's length on, the pool holds K/V of OTHER tokens, so what
        # the call fails to write or to mask shows in the logits
        hist = jnp.where(np.arange(M)[None] < starts[:, None],
                         tokens[:, :M], other)
        _, pool = model.apply(
            {"params": params}, hist,
            cache=dict(noise, idx=jnp.zeros((B,), jnp.int32)),
            chunked_prefill=True)
        new_toks = jnp.stack([tokens[b, n:n + L]
                              for b, n in enumerate(starts)])
        want = np.stack([full[b, n:n + L] for b, n in enumerate(starts)])
        logits, new = model.apply(
            {"params": params}, new_toks,
            cache=dict(pool, idx=jnp.asarray(idx, jnp.int32)),
            chunked_prefill=chunked)
        np.testing.assert_array_equal(np.asarray(new["idx"]),
                                      np.asarray(idx) + L)
        return (np.asarray(logits), want,
                {k: np.asarray(pool[k]) for k in "kv"},
                {k: np.asarray(new[k]) for k in "kv"}, starts, L)
    return run


_PARITY_CASES = [
    pytest.param(scan, experts, mode,
                 id=f"{'scan' if scan else 'unrolled'}-"
                    f"{'moe' if experts else 'dense'}-{mode}")
    for scan in (True, False) for experts in (0, 4)
    for mode in _PARITY_MODES]


@pytest.mark.parametrize("scan_layers,n_experts,mode", _PARITY_CASES)
def test_cached_forward_matches_uncached(parity_run, scan_layers,
                                         n_experts, mode):
    """Logits of the cached forward equal the full causal forward's at
    the same positions, row by row, over a pool whose unused positions
    hold noise."""
    logits, want, *_ = parity_run(scan_layers, n_experts, mode)
    np.testing.assert_allclose(logits, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("scan_layers,n_experts,mode", _PARITY_CASES)
def test_cached_forward_touches_only_its_rows(parity_run, scan_layers,
                                              n_experts, mode):
    """Across the call the pool changes at [idx_b, idx_b + L) of row b
    in every layer and nowhere else: other rows' positions, a row's
    history and everything above idx + L are bit-identical."""
    _, _, before, after, starts, L = parity_run(scan_layers, n_experts,
                                                mode)
    written = np.zeros(before["k"].shape, bool)
    for b, n in enumerate(starts):
        written[:, b, n:n + L] = True
    for name in "kv":
        np.testing.assert_array_equal(after[name][~written],
                                      before[name][~written])
        # and the new rows did land (noise before, K/V after)
        assert (after[name][written] != before[name][written]).mean() > 0.99
