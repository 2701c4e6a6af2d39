"""ops/tile_attention.py on the CPU (`interpret=True`): the Pallas kernel a
prefill tile attends its scratch through, against `_tile_attention`'s XLA
loop (its step-for-step reference, and what runs off the TPU) AND against a
plain float32 masked softmax over the positions themselves.

Small sizes, the real head width: D 128, tiles of 256 rows in blocks of 128
(two blocks of query rows, so each walks a range of its own), rings of 640
places in key blocks of 128 under windows of 384 and 300, caches by
position of 1,280 places in key blocks of 256, G 6 and 5. One test holds
the walk itself: the first block and the count against a count over the
pairs, and every place outside the walked blocks poisoned.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import sparse_attention as sa
from ray_tpu.models import transformer as tr
from ray_tpu.ops import tile_attention as ta

D, S, ROWS = 128, 256, 128
RING, CACHE = 640, 1280
LONG_RING = 896         # seven key blocks, of which a tile walks five or six


def _case(pos0, M, window, H=12, Hkv=2, dtype=jnp.float32, seed=0):
    """(q, the scratch's K and V holding positions up to the tile's last,
    every position's K and V) of a tile at pos0: a ring holds position p
    at p mod M, the newest over the older."""
    n = pos0 + S
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, S, H, D), jnp.float32).astype(dtype)
    k_all = jax.random.normal(ks[1], (1, n, Hkv, D)).astype(dtype)
    v_all = jax.random.normal(ks[2], (1, n, Hkv, D)).astype(dtype)
    at = np.arange(max(0, n - M), n) if window else np.arange(n)
    place = at % M if window else at
    kc = jnp.zeros((1, M, Hkv, D), dtype).at[:, place].set(k_all[:, at])
    vc = jnp.zeros((1, M, Hkv, D), dtype).at[:, place].set(v_all[:, at])
    return q, kc, vc, k_all, v_all


def _plain(q, k_all, v_all, pos0, window):
    """softmax(q k^T / sqrt(D)) v over the positions a row may see, whole,
    in float32 at the highest precision."""
    _, _, H, _ = q.shape
    n, Hkv = k_all.shape[1:3]
    qpos = pos0 + np.arange(S)[:, None]
    kpos = np.arange(n)[None, :]
    ok = kpos <= qpos
    if window:
        ok &= kpos > qpos - window
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    s = jnp.einsum("bshgd,bmhd->bhgsm",
                   f32(q).reshape(1, S, Hkv, H // Hkv, D), f32(k_all),
                   precision="highest") * D ** -0.5
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    return np.asarray(jnp.einsum("bhgsm,bmhd->bshgd", p, f32(v_all),
                                 precision="highest").reshape(1, S, H, D))


def _three(pos0, M, window, max_rows=ROWS, **kw):
    q, kc, vc, k_all, v_all = _case(pos0, M, window, **kw)
    assert ta.fits(S, M, q.shape[2], kc.shape[2], D, window)
    assert not sa._tile_kernel_takes(S, M, q.shape[2], kc.shape[2], D,
                                     window)                 # the CPU
    at = jnp.int32(pos0)
    kernel = ta.tile_attention(q, kc, vc, at, window, max_rows=max_rows,
                               interpret=True)
    loop = tr._tile_attention(q, kc, vc, at, window)
    assert kernel.dtype == q.dtype and kernel.shape == q.shape
    return (np.asarray(kernel.astype(jnp.float32)),
            np.asarray(loop.astype(jnp.float32)),
            _plain(q, k_all, v_all, pos0, window))


RING_STARTS = {
    "under-the-window": 0,
    "at-the-window": 128,
    "wrapped-once-straddles-the-end": 512,      # rows 512..767 of 640
    "wrapped-more-than-twice": 1536,
    "odd-multiple-of-the-block": 384 + 2 * RING,
    "no-multiple-of-the-block": 1937,
    "straddles-the-end-at-no-multiple": 2 * RING + 500,
}


@pytest.mark.parametrize("window", [384, 300])
@pytest.mark.parametrize("pos0", list(RING_STARTS.values()),
                         ids=list(RING_STARTS))
def test_a_tile_against_its_ring(pos0, window):
    kernel, loop, plain = _three(pos0, RING, window)
    np.testing.assert_allclose(kernel, loop, atol=2e-6, rtol=0)
    np.testing.assert_allclose(kernel, plain, atol=2e-5, rtol=0)


@pytest.mark.parametrize("pos0", [0, 256, 600, 1024])
def test_a_tile_against_a_cache_by_position(pos0):
    kernel, loop, plain = _three(pos0, CACHE, 0)
    np.testing.assert_allclose(kernel, loop, atol=2e-6, rtol=0)
    np.testing.assert_allclose(kernel, plain, atol=2e-5, rtol=0)


@pytest.mark.parametrize("H,Hkv", [(12, 2), (10, 2), (4, 4)],
                         ids=["G6", "G5", "G1"])
@pytest.mark.parametrize("M,window,pos0", [(RING, 384, 1000),
                                           (CACHE, 0, 700)],
                         ids=["ring", "by-position"])
def test_every_query_head_of_a_kv_head_meets_its_keys(H, Hkv, M, window,
                                                      pos0):
    """G query heads a KV head, one block of all 256 rows and two of 128:
    K and V are not repeated, a head's lanes of q meet its KV head's."""
    for rows in (ROWS, S):
        kernel, loop, plain = _three(pos0, M, window, rows, H=H, Hkv=Hkv)
        np.testing.assert_allclose(kernel, loop, atol=2e-6, rtol=0)
        np.testing.assert_allclose(kernel, plain, atol=2e-5, rtol=0)


@pytest.mark.parametrize("M,window,pos0", [(RING, 384, 1408),
                                           (CACHE, 0, 512)],
                         ids=["ring", "by-position"])
def test_bf16_as_served(M, window, pos0):
    """bf16 operands, float32 products and statistics, the probabilities
    cast for p @ v: the loop's arithmetic, so the two differ by the order
    of float32 sums, under one bf16 rounding of the result."""
    kernel, loop, plain = _three(pos0, M, window, dtype=jnp.bfloat16)
    np.testing.assert_allclose(kernel, loop, atol=4e-3, rtol=0)
    np.testing.assert_allclose(kernel, plain, atol=2e-2, rtol=0)


@pytest.mark.parametrize("M,window,pos0", [(RING, 384, 1280),
                                           (CACHE, 0, 768)],
                         ids=["ring", "by-position"])
def test_a_last_tile_with_padded_rows(M, window, pos0):
    """A prompt's last tile: 100 real rows, the rest padding whose keys
    and values the scratch was written with too (here: huge ones). A real
    row sees no padded key, so it reads what it reads without them."""
    real = 100
    q, kc, vc, k_all, v_all = _case(pos0, M, window)
    pad = (pos0 + real + np.arange(S - real)) % M
    kc, vc = kc.at[:, pad].set(1e4), vc.at[:, pad].set(-1e4)
    at = jnp.int32(pos0)
    kernel = ta.tile_attention(q, kc, vc, at, window, max_rows=ROWS,
                               interpret=True)
    loop = tr._tile_attention(q, kc, vc, at, window)
    np.testing.assert_allclose(kernel[:, :real], loop[:, :real], atol=2e-6,
                               rtol=0)
    np.testing.assert_allclose(kernel, loop, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(
        kernel[:, :real], _plain(q, k_all, v_all, pos0, window)[:, :real],
        atol=2e-5, rtol=0)


OWN = {"ring-under-the-window": (RING, 384, 0),
       "ring-straddles-the-end": (RING, 384, 2 * RING + 500),
       "ring-at-no-multiple": (RING, 300, 1937),
       "by-position": (CACHE, 0, 600)}


@pytest.mark.parametrize("M,window,pos0", list(OWN.values()), ids=list(OWN))
def test_the_tiles_own_rows_are_written_into_the_kernels_view(
        monkeypatch, M, window, pos0):
    """`_tile_attention(.., own=(k, v))` as `Attention._in_place` calls
    it: the caches hold the positions below the tile, and the tile's own
    rows are written first. Where the kernel takes the tile they are
    written into the caches AS THE KERNEL READS THEM, [1, M, Hkv * D] (a
    ring's modulo the ring, a tile that straddles its end in two pieces):
    the predicate is made to answer as on the chip and the kernel to
    interpret. Both forms give what the loop gives on caches written
    beforehand."""
    import functools
    q, kc, vc, k_all, v_all = _case(pos0, M, window)
    own = (k_all[:, pos0:], v_all[:, pos0:])
    # the caches before the tile: its own places hold something else
    at = (pos0 + np.arange(S)) % M if window else pos0 + np.arange(S)
    k_old, v_old = kc.at[:, at].set(7.0), vc.at[:, at].set(-7.0)
    pos = jnp.int32(pos0)
    want = tr._tile_attention(q, kc, vc, pos, window)
    loop = tr._tile_attention(q, k_old, v_old, pos, window, own=own)
    np.testing.assert_array_equal(np.asarray(loop), np.asarray(want))
    monkeypatch.setattr(sa, "_tile_kernel_takes", ta.fits)
    monkeypatch.setattr(ta, "tile_attention", functools.partial(
        ta.tile_attention, max_rows=ROWS, interpret=True))
    kernel = tr._tile_attention(q, k_old, v_old, pos, window, own=own)
    np.testing.assert_allclose(kernel, want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(kernel, _plain(q, k_all, v_all, pos0, window),
                               atol=2e-5, rtol=0)


WALKS = [(RING, 384, 0), (LONG_RING, 384, 512), (LONG_RING, 300, 1937),
         (LONG_RING, 384, 2 * LONG_RING + 700), (CACHE, 0, 0),
         (CACHE, 0, 600), (CACHE, 0, 1024)]


@pytest.mark.parametrize("M,window,pos0", WALKS)
def test_the_blocks_walked_are_the_blocks_that_hold_a_visible_pair(
        M, window, pos0):
    """`block_walk` against a count over the pairs: a block of query rows
    walks from the first block of positions that holds a key one of its
    rows sees to the last, and no further; and the kernel reads and
    computes no other: every place of the scratch outside the walked
    blocks holds NaN, and no NaN reaches the output."""
    bq, kb = ta.blocks_of(S, M, 6, ROWS)
    first, count = (np.asarray(a) for a in ta.block_walk(
        jnp.int32(pos0), S, M, window, bq, kb))
    assert max(count) <= ta.max_steps(M, window, bq, kb)
    walked = set()
    for j in range(S // bq):
        qpos = pos0 + j * bq + np.arange(bq)[:, None]
        kpos = np.arange(pos0 + S)[None, :]
        ok = kpos <= qpos
        if window:
            ok &= kpos > qpos - window
        blocks = np.unique(np.nonzero(ok.any(0))[0] // kb)
        assert (first[j], count[j]) == (blocks[0], len(blocks))
        assert np.array_equal(blocks, first[j] + np.arange(count[j]))
        walked |= {int(c) % (M // kb) if window else int(c) for c in blocks}
    q, kc, vc, k_all, v_all = _case(pos0, M, window)
    unread = np.asarray([p for p in range(M) if p // kb not in walked], int)
    assert len(unread) >= kb or pos0 + S == M == CACHE
    kc, vc = kc.at[:, unread].set(np.nan), vc.at[:, unread].set(np.nan)
    out = np.asarray(ta.tile_attention(q, kc, vc, jnp.int32(pos0), window,
                                       max_rows=ROWS, interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, _plain(q, k_all, v_all, pos0, window),
                               atol=2e-5, rtol=0)


def test_what_the_kernel_takes_and_what_it_refuses():
    """The predicate is shapes: D one lane tile, the tile whole 128-row
    blocks, key blocks of whole lane tiles, a ring that holds tile and
    window. Trinity's and Falcon-H1's tiles fit; a vector of starts, two
    tiles at once and an odd shape go to the loop (and raise by name)."""
    assert ta.fits(1024, 5120, 48, 8, 128, 4096)        # Trinity's rings
    assert ta.fits(1024, 26624, 48, 8, 128)             # its full layer
    assert ta.fits(1024, 9216, 20, 4, 128)              # Falcon-H1's heads
    assert ta.blocks_of(1024, 5120, 6) == (512, 512)
    assert ta.max_steps(5120, 4096, 512, 512) == 10
    assert not ta.fits(1024, 5120, 48, 8, 64, 4096)     # D
    assert not ta.fits(8, 24, 6, 2, 128, 16)            # a CPU test's tile
    assert not ta.fits(1024, 5120 + 64, 48, 8, 128)     # key blocks of 64
    assert not ta.fits(1024, 4608, 48, 8, 128, 4096)    # the ring too short
    q, kc, vc, _, _ = _case(0, RING, 384)
    with pytest.raises(ValueError, match="one start"):
        ta.tile_attention(q, kc, vc, jnp.zeros((1,), jnp.int32), 384,
                          interpret=True)
    two = jnp.concatenate([q, q])
    with pytest.raises(ValueError, match="one tile"):
        ta.tile_attention(two, kc, vc, jnp.int32(0), 384, interpret=True)
    # the loop takes both, as before
    assert tr._tile_attention(two, jnp.concatenate([kc, kc]),
                              jnp.concatenate([vc, vc]),
                              jnp.zeros((2,), jnp.int32), 384).shape \
        == two.shape
