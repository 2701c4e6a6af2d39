"""KV-cache decode + autoregressive generation (the serving inference
engine; reference ships no model code — parity target is the decode
correctness contract every inference stack owes: cached stepwise logits
must equal the full causal forward).

Runs on the CPU (conftest): a chip's bf16 default matmuls would turn
these exactness checks into noise comparisons."""

import dataclasses

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
