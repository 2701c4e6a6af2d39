"""The dense model's decode row (a model without an indexer and without
kinds of layer: Mistral, Mixtral, the debug models) attends K and V in the
slots' pools where they lie (`models/transformer.py` `_row_attention`, the
form "att", "win" and "hyb" layers take), its own key and value folded into
the running softmax, and no longer a layer sliced out of the pool,
rewritten with the new rows and scored over all `max_len` positions by
`_cached_attention`. CPU, float32:

- the row through `_row_attention` equals `_cached_attention` over the
  same cache at 8, 4 and 2 KV heads, at lengths 0, 1, a key block's edge,
  `max_len - 1`, with an idle slot among the live ones, by the XLA loop
  and by the Pallas kernel interpreted;
- the kernel, interpreted, at Mistral's pools' shape gives the XLA loop's
  running softmax;
- the cached forward hands a row at a vector of lengths the pools whole and
  gives what the scalar form (`generate.py`'s, `_cached_attention`) gives;
  the scanned and the unscanned layer loops serve the same tokens;
- the engine counts what the rows read and attend (`kv_rows_streamed`,
  `kv_rows_live`), and only for a model whose rows take this path.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from ray_tpu.models import sparse_attention as sa
from ray_tpu.models import transformer as tr
from ray_tpu.ops import decode_attention
from tests.test_fused_step import engine_of, model_of

M, D, H = 1024, 128, 8          # two key blocks of 512; one lane tile
# slot 5 is idle: a stale length, and what its last owner left behind
LENS = [0, 1, 511, 512, 513, 700, M - 1]
IDLE = 5


def _case(Hkv, dtype=jnp.float32):
    B = len(LENS)
    ks = jax.random.split(jax.random.PRNGKey(Hkv), 5)
    q = jax.random.normal(ks[0], (B, 1, H, D), dtype)
    k_new, v_new = (jax.random.normal(k, (B, 1, Hkv, D), dtype)
                    for k in ks[1:3])
    # three layers: the row attends layer 1, the others hold other noise
    k_pool, v_pool = (jax.random.normal(k, (3, B, M, Hkv, D), dtype)
                      for k in ks[3:5])
    return q, k_new, v_new, k_pool, v_pool, jnp.asarray(LENS, jnp.int32)


@pytest.fixture
def kernel_interpreted(monkeypatch):
    """The row takes the Pallas kernel wherever the pools' shape fits it,
    interpreted (as on the chip, where the backend says so)."""
    monkeypatch.setattr(sa, "_kernel_reads", decode_attention.fits)
    monkeypatch.setattr(
        decode_attention, "pool_decode_attention", functools.partial(
            decode_attention.pool_decode_attention, interpret=True))


@pytest.mark.parametrize("form", ["loop", "kernel"])
@pytest.mark.parametrize("Hkv", [8, 4, 2])
def test_the_row_in_place_is_the_cached_attention(Hkv, form, request):
    """What the dense row computed until PR 47 is the oracle: the layer
    sliced out, the rows written into it at the slots' lengths, every
    position scored. A live slot's row is the same whatever the idle slot
    holds; the idle slot's row is some finite number nobody reads."""
    assert decode_attention.fits(M, Hkv, D)     # 2 heads: 1,024 rows a block
    if form == "kernel":
        request.getfixturevalue("kernel_interpreted")
    q, k_new, v_new, k_pool, v_pool, lens = _case(Hkv)
    want = tr._cached_attention(
        q, tr._cache_write(k_pool[1], k_new, lens),
        tr._cache_write(v_pool[1], v_new, lens), lens)

    row = jax.jit(lambda *a: tr._row_attention(*a[:5], jnp.int32(1), a[5]))
    got = row(q, k_new, v_new, k_pool, v_pool, lens)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    live = np.arange(len(LENS)) != IDLE
    spoiled = row(q, k_new, v_new, k_pool.at[:, IDLE].set(1e4),
                  v_pool.at[:, IDLE].set(-1e4), lens.at[IDLE].set(M))
    assert (np.asarray(spoiled)[live] == np.asarray(got)[live]).all()
    assert np.isfinite(np.asarray(spoiled)).all()
    # a row at length 0 attends its own key alone
    np.testing.assert_allclose(
        got[0, 0], jnp.repeat(v_new[0, 0], H // Hkv, axis=0), atol=1e-6)


def test_pool_kernel_interpreted_at_mistrals_shape_is_the_xla_loop():
    """ops/decode_attention.py at `mistral-7b.chat-steady`'s pools (16
    slots of 2,048 positions, 8 KV heads of 128; two layers of the twenty),
    bf16 as the cell keeps them: the kernel, interpreted, against the XLA
    loop, slots idle, at a block's edge, mid-block and full."""
    assert decode_attention.fits(2048, 8, 128)
    ks = jax.random.split(jax.random.PRNGKey(47), 3)
    q = jax.random.normal(ks[0], (16, 32, 128), jnp.bfloat16)
    kp, vp = (jax.random.normal(k, (2, 16, 2048, 8, 128), jnp.bfloat16)
              for k in ks[1:])
    lens = jnp.asarray([0, 1, 37, 400, 511, 512, 513, 1024, 1500, 2047,
                        0, 96, 352, 288, 2048, 640], jnp.int32)
    args = (q, kp, vp, jnp.int32(1), lens)
    m, l, acc = decode_attention.pool_decode_attention(*args, interpret=True)
    m0, l0, acc0 = jax.jit(decode_attention.pool_decode_reference)(*args)
    some = np.asarray(lens) > 0
    np.testing.assert_allclose(m, m0, atol=1e-6)
    np.testing.assert_allclose(l, l0, rtol=1e-4)
    np.testing.assert_allclose(
        (acc / jnp.maximum(l, 1e-30)[..., None])[some],
        (acc0 / jnp.maximum(l0, 1e-30)[..., None])[some], atol=5e-3)
    assert float(jnp.abs(acc[~some]).max()) == 0.0   # nothing attended


@functools.lru_cache(maxsize=None)
def _unscanned(kind):
    """`model_of(kind)` with its layers unrolled, on the same weights."""
    model, params = model_of(kind)
    params = meta.unbox(params)
    flat = {k: v for k, v in params.items() if k != "layers"}
    for i in range(model.cfg.n_layers):
        flat[f"layer_{i}"] = jax.tree.map(lambda a: a[i],
                                          params["layers"]["block"])
    return tr.TransformerLM(dataclasses.replace(
        model.cfg, scan_layers=False)), flat


@pytest.mark.parametrize("scan", [True, False])
def test_rows_at_their_own_lengths_read_the_pools_whole(scan, monkeypatch):
    """The cached forward of one row a slot at a VECTOR of lengths goes
    through `_row_attention` with the whole pools and every layer's number,
    and gives the logits and the new cache of the same rows taken one slot
    at a time at a scalar length, which `_cached_attention` serves against
    the layer sliced out (`generate.py`'s form)."""
    model, params = model_of("dense") if scan else _unscanned("dense")
    cfg, S, L = model.cfg, 4, 64
    seen = []
    row = tr._row_attention
    monkeypatch.setattr(tr, "_row_attention", lambda q, k, v, kp, vp, layer,
                        lens, *a: seen.append((kp.shape, jnp.shape(lens)))
                        or row(q, k, v, kp, vp, layer, lens, *a))
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    pools = {n: jax.random.normal(k, tr.kv_cache_shape(cfg, S, L))
             for n, k in zip("kv", ks)}
    lens = jnp.asarray([0, 17, 63, 5], jnp.int32)
    toks = jnp.asarray([[3], [9], [27], [81]])
    logits, new = model.apply({"params": params}, toks,
                              cache=dict(pools, idx=lens))
    # (a scanned body is traced more than once; an unrolled layer once)
    assert set(seen) == {(tr.kv_cache_shape(cfg, S, L), (S,))}
    assert scan or len(seen) == cfg.n_layers
    seen.clear()
    for b in range(S):
        one = {n: p[:, b:b + 1] for n, p in pools.items()}
        lg, nw = model.apply({"params": params}, toks[b:b + 1],
                             cache=dict(one, idx=lens[b]))
        np.testing.assert_allclose(logits[b], lg[0], atol=2e-5, rtol=1e-5)
        for n in "kv":
            at = min(int(lens[b]), L - 1)
            np.testing.assert_allclose(new[n][:, b, at], nw[n][:, 0, at],
                                       atol=1e-5)
    assert not seen                 # a scalar start: the small-cache form


def test_scanned_and_unscanned_layer_loops_serve_the_same_tokens():
    """Mixed traffic (tests/test_step_order.py's: tiles with rows behind
    them, rows alone, a prefix hit, an eviction) through the engine of the
    dense model with its layers scanned and with them unrolled: the same
    greedy tokens, and those pinned on the parent's tree."""
    from ray_tpu.inference import EngineConfig, InferenceEngine
    from tests.test_step_order import PINNED, _traffic
    eos, _, want = PINNED["dense"]
    model, params = _unscanned("dense")
    eng = InferenceEngine(model, params, EngineConfig(
        n_slots=4, max_len=64, prefill_chunk=4, prefill_budget=8,
        prefix_cache_slots=2))
    assert _traffic(eng, eos) == want


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_engine_counts_what_the_rows_read_and_attend(kind):
    """`kv_rows_streamed`: whole key blocks up to each decode row's last
    live one and the row's own (`decode_positions_read`; on the CPU the XLA
    loop reads up to the LONGEST live row's for every row); `kv_rows_live`:
    the positions the row attends, its own among them."""
    eng = engine_of(kind)
    block = decode_attention.block_of(64)
    h = eng.submit(np.arange(1, 10), max_new_tokens=4)      # 9 positions
    h2 = eng.submit(np.arange(1, 21), max_new_tokens=3)     # 20
    while eng.step():
        pass
    assert len(list(h)) == 4 and len(list(h2)) == 3
    st = eng.stats()
    # a prompt's last tile samples its first token; the rows after it sit
    # at lengths 9, 10, 11 and 20, 21: each attends its length + 1
    assert st["kv_rows_live"] == (10 + 11 + 12) + (21 + 22)
    rows = 3 + 2
    assert st["kv_rows_streamed"] == rows * (block + 1)
    assert 1.0 < st["kv_rows_streamed"] / st["kv_rows_live"]


@pytest.mark.parametrize("kind", ["indexer"])
def test_no_count_where_the_rows_go_another_way(kind):
    """An indexer's rows have their own counters (`dsa_rows_*`)."""
    st = engine_of(kind).stats()
    assert "kv_rows_streamed" not in st and "kv_rows_live" not in st
