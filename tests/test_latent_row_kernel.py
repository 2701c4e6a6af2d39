"""The latent kernel of ops/decode_attention.py on the CPU (`interpret=True`):
the Pallas kernel an "mla" layer's decode rows read their slots' latents
through, against `latent_attention.row_attention`'s XLA loop (its
step-for-step reference, and what runs off the TPU) AND against a plain
float32 softmax over K and V expanded for every live position.

Small sizes, the real widths of a head: an unrotated key of 128, a rotated
one of 64 that all heads share, values of 128, latents of 128 (a position
keeps 192 values); 8 heads, 4 slots of 1,024 positions in a pool of two
layers, read in blocks of 128, 256 and 512.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import latent_attention as la
from ray_tpu.models import sparse_attention as sa
from ray_tpu.ops import decode_attention as da

R, DN, DR, DV = 128, 128, 64, 128
W, H, B, M, LAYERS = R + DR, 8, 4, 1024, 2
SCALE = 1.37 * (DN + DR) ** -0.5        # a YaRN factor squared beside it


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


def _case(lens, layer, dtype, block, seed=0):
    """(q [B, 1, H, Dn + Dr], the rows' own latents [B, 1, W], the pool with
    finite numbers everywhere, the same pool with NaN wherever no kernel
    may read (the other layer, and of a slot every block past its last
    live one: all of a slot that holds nothing), w_uk, w_uv)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, 1, H, DN + DR)).astype(dtype)
    own = jax.random.normal(ks[1], (B, 1, W)).astype(dtype)
    pool = jax.random.normal(ks[2], (LAYERS, B, W, M)).astype(dtype)
    w = (jax.random.normal(ks[3], (R, H, DN + DV)) * R ** -0.5).astype(dtype)
    read = -(-np.asarray(lens) // block) * block                   # [B]
    dead = np.arange(M)[None, :] >= read[:, None]                  # [B, M]
    poisoned = jnp.where(dead[None, :, None, :]
                         | (np.arange(LAYERS) != layer)[:, None, None, None],
                         jnp.nan, pool)
    return q, own, pool, poisoned, w[..., :DN], w[..., DN:]


def _plain(q, own, pool, layer, lens, w_uk, w_uv):
    """softmax(scale q . [W_uk c ‖ k_r]) (W_uv c) over a slot's live
    positions and the row's own, whole, in float32 at the highest
    precision -> [B, 1, H, Dv]."""
    f32 = lambda a: np.asarray(a, np.float32)                # noqa: E731
    out = []
    for b, n in enumerate(lens):
        lat = np.concatenate([f32(pool[layer, b, :, :n]),
                              f32(own[b]).T], axis=1)              # [W, n+1]
        k = np.einsum("rm,rhd->mhd", lat[:R], f32(w_uk))
        v = np.einsum("rm,rhd->mhd", lat[:R], f32(w_uv))
        s = (np.einsum("hd,mhd->hm", f32(q[b, 0, :, :DN]), k)
             + np.einsum("hd,dm->hm", f32(q[b, 0, :, DN:]), lat[R:])) * SCALE
        p = np.exp(s - s.max(axis=1, keepdims=True))
        out.append(np.einsum("hm,mhd->hd", p / p.sum(axis=1, keepdims=True),
                             v))
    return np.stack(out)[:, None]


def _through_the_kernel(monkeypatch, block, calls=None):
    """`row_attention`'s predicate made to answer as on the chip and the
    kernel made to interpret, reading blocks of `block`."""
    compiled = da.latent_pool_decode_attention

    def interpreted(qa, *a):
        if calls is not None:
            calls.append(qa.shape)
        return compiled(qa, *a, max_block=block, interpret=True)

    monkeypatch.setattr(sa, "_latent_row_kernel_takes", da.latent_fits)
    monkeypatch.setattr(da, "latent_pool_decode_attention", interpreted)


# a block's length b stands for itself in a case's lengths
CASES = {
    "uneven-lengths": (lambda b: [300, 77, 901, 513], 1),
    "the-first-layer": (lambda b: [640, 3, 129, 1000], 0),
    "an-empty-slot-among-live-ones": (lambda b: [300, 0, 901, 0], 1),
    "empty-slots-before-the-first-live": (lambda b: [0, 0, 40, 0], 1),
    "no-slot-live": (lambda b: [0, 0, 0, 0], 1),
    "at-a-blocks-edge-and-one-past-it": (lambda b: [b, b + 1, M - b,
                                                    M - b + 1], 1),
    "a-slot-full-to-its-last-position": (lambda b: [M, 5, M, 1], 1),
}
# float32 holds the arithmetic to the loop's; bf16 is what is served: the
# probabilities rounded as the loop's einsum rounds them, summed in blocks
# of another length
TOL = {jnp.float32: (3e-6, 3e-5), jnp.bfloat16: (1.6e-2, 4e-2)}


@pytest.mark.parametrize("block", [128, 256, 512])
@pytest.mark.parametrize("dtype", list(TOL), ids=["float32", "bf16"])
@pytest.mark.parametrize("lens,layer", list(CASES.values()), ids=list(CASES))
def test_the_kernel_is_the_loop_and_the_plain_softmax(monkeypatch, lens,
                                                      layer, dtype, block):
    """The rows through `row_attention` twice: by its loop over a pool of
    finite numbers, and by the kernel over the same pool with NaN in every
    place the kernel has no business reading; no NaN comes out."""
    lens = lens(block)
    q, own, pool, poisoned, w_uk, w_uv = _case(lens, layer, dtype, block)
    assert da.latent_fits(M, H, W, R) and da.block_of(M, block) == block
    assert not sa._latent_row_kernel_takes(M, H, W, R)              # CPU
    at, n = jnp.int32(layer), jnp.asarray(lens, jnp.int32)
    loop = la.row_attention(q, own, pool, at, n, w_uk, w_uv, SCALE)
    calls = []
    _through_the_kernel(monkeypatch, block, calls)
    kernel = la.row_attention(q, own, poisoned, at, n, w_uk, w_uv, SCALE)
    assert calls == [(B, H, W)]
    assert kernel.dtype == q.dtype and kernel.shape == (B, 1, H, DV)
    near, far = TOL[dtype]
    np.testing.assert_allclose(_f32(kernel), _f32(loop), atol=near, rtol=0)
    np.testing.assert_allclose(
        _f32(kernel), _plain(q, own, pool, layer, lens, w_uk, w_uv),
        atol=far, rtol=0)


def test_the_running_softmax_comes_back_unnormalised():
    """(largest score, sum, weighted latents) of the pool's positions
    alone, float32: what `fold` carries after the loop's last block; a
    slot that holds nothing gives the empty softmax."""
    lens = [300, 0, 901, 64]
    q, own, pool, poisoned, w_uk, _ = _case(lens, 1, jnp.float32, 256)
    qa = jnp.concatenate([jnp.einsum("bhd,rhd->bhr", q[:, 0, :, :DN], w_uk),
                          q[:, 0, :, DN:]], axis=-1)
    m, l, acc = da.latent_pool_decode_attention(
        qa, poisoned, jnp.int32(1), jnp.asarray(lens), R, SCALE,
        max_block=256, interpret=True)
    assert (m.shape, l.shape, acc.shape) == ((B, H), (B, H), (B, H, R))
    assert {a.dtype for a in (m, l, acc)} == {jnp.dtype(jnp.float32)}
    for b, n in enumerate(lens):
        s = np.einsum("hw,wn->hn", np.asarray(qa[b]),
                      np.asarray(pool[1, b, :, :n])) * SCALE
        if not n:
            assert (np.asarray(m[b]) == da.NEG_INF).all()
            assert not np.asarray(l[b]).any() and not np.asarray(acc[b]).any()
            continue
        np.testing.assert_allclose(m[b], s.max(axis=1), atol=2e-5)
        p = np.exp(s - s.max(axis=1, keepdims=True))
        np.testing.assert_allclose(l[b], p.sum(axis=1), rtol=2e-5)
        np.testing.assert_allclose(
            acc[b], p @ np.asarray(pool[1, b, :R, :n]).T, atol=2e-4)


def test_a_query_wider_than_the_pool_meets_it_as_the_loop_does(monkeypatch):
    """A pool kept in bf16 under a model in float32 (`cache_dtype`): the
    loop's einsum widens the block to the query's type, and so does the
    kernel."""
    lens = [300, 0, 901, 513]
    q, own, pool, poisoned, w_uk, w_uv = _case(lens, 1, jnp.float32, 256)
    pool, poisoned = (a.astype(jnp.bfloat16) for a in (pool, poisoned))
    at, n = jnp.int32(1), jnp.asarray(lens)
    loop = la.row_attention(q, own, pool, at, n, w_uk, w_uv, SCALE)
    _through_the_kernel(monkeypatch, 256)
    kernel = la.row_attention(q, own, poisoned, at, n, w_uk, w_uv, SCALE)
    assert kernel.dtype == jnp.float32
    np.testing.assert_allclose(kernel, loop, atol=1.6e-2, rtol=0)


TAKES = {
    # (M, H, W, R)
    "the-cells": ((18432, 64, 576, 512), True, 2048),
    "these-tests": ((M, H, W, R), True, 1024),
    "a-slot-of-whole-blocks-of-128": ((18432 + 128, 64, 576, 512), True, 128),
    "a-rotated-key-of-a-whole-lane-tile": ((M, H, 256, 128), True, 1024),
    "positions-no-multiple-of-128": ((18432 + 64, 64, 576, 512), False, 64),
    "a-slot-of-104": ((104, 4, 40, 32), False, 8),
    "values-no-multiple-of-16": ((M, H, 200, 128), False, 1024),
    "latents-of-no-whole-lane-tile": ((M, H, 256, 192), False, 1024),
    "latents-wider-than-a-position": ((M, H, 128, 256), False, 1024),
    "six-heads": ((M, 6, W, R), False, 1024),
}


@pytest.mark.parametrize("shape,takes,block", list(TAKES.values()),
                         ids=list(TAKES))
def test_what_the_latent_kernel_takes_and_what_it_refuses(shape, takes,
                                                          block):
    """The predicate is shapes; on the CPU its twin in
    models/sparse_attention.py refuses everything."""
    assert da.latent_fits(*shape) is takes
    assert da.latent_block_of(shape[0]) == block
    assert not sa._latent_row_kernel_takes(*shape)


def test_the_kernel_refuses_a_pool_that_is_not_its_rows():
    q, own, pool, _, w_uk, _ = _case([5] * B, 0, jnp.float32, 128)
    qa = jnp.zeros((B, H, W), jnp.float32)
    call = functools.partial(da.latent_pool_decode_attention, layer=0,
                             lens=jnp.zeros((B,), jnp.int32), scale=SCALE,
                             interpret=True)
    for bad in (lambda: call(qa, pool[..., :-64], R=R),
                lambda: call(qa, pool[:, :2], R=R),
                lambda: call(qa[..., :-16], pool, R=R),
                lambda: call(qa, pool, R=96),
                lambda: call(qa[:, :6], pool, R=R)):
        with pytest.raises(ValueError, match="latent decode kernel takes"):
            bad()


def test_the_counter_follows_the_predicate_and_the_kernels_block(
        monkeypatch):
    """`mla_rows_streamed`'s arithmetic at the cell's size: where the loop
    runs, the longest row's blocks of 512 for every row; where the kernel
    reads, each row's own blocks of 2,048, of an empty slot none; the
    row's own position either way."""
    lens, shape = [7000, 0, 9000], (18432, 64, 576, 512)
    assert sa.latent_positions_read(lens, *shape) == 3 * (9216 + 1)
    assert sa.latent_positions_read([], *shape) == 0
    monkeypatch.setattr(sa, "_latent_row_kernel_takes", da.latent_fits)
    assert sa.latent_positions_read(lens, *shape) \
        == (8192 + 1) + (0 + 1) + (10240 + 1)
    assert sa.latent_positions_read([], *shape) == 0
    # a pool the kernel does not take keeps the loop's count
    assert sa.latent_positions_read(lens, 18432 + 64, 64, 576, 512) \
        == 3 * (9024 + 1)


def test_one_pallas_call_a_shape():
    """A stack's layers share one built `pallas_call` (`_latent_call`'s
    cache), as the tile's kernels do."""
    args = (B, M, H, W, R, 256, SCALE, True)
    assert da._latent_call(*args) is da._latent_call(*args)
