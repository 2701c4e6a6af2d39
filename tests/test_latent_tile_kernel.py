"""The latent kernel of ops/tile_attention.py on the CPU (`interpret=True`):
the Pallas kernel an "mla" layer's prefill tile attends its scratch through,
against `latent_attention.tile_attention`'s XLA loop (its step-for-step
reference, and what runs off the TPU) AND against a plain float32 causal
softmax over K and V expanded for every position.

Small sizes, the real widths of a head: an unrotated key of 128, a rotated
one of 64 that all heads share, values of 128, latents of 128; tiles of 256
rows against a scratch of 1,280 positions in key blocks of 256; 4 heads (one
group of the kernel's grid) and 8 (two).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import latent_attention as la
from ray_tpu.models import sparse_attention as sa
from ray_tpu.models.transformer import _cache_write
from ray_tpu.ops import tile_attention as ta

R, DN, DR, DV = 128, 128, 64, 128
S, M = 256, 1280
SCALE = 1.37 * (DN + DR) ** -0.5        # a YaRN factor squared beside it


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


def _case(pos0, H, dtype, seed=0, real=S):
    """(q, the scratch with the positions below the tile, the tile's own
    latents, w_uk, w_uv, every position's latents [W, pos0 + S]); of the
    tile's S rows the first `real` are a prompt's, the others padding."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = pos0 + S
    q = jax.random.normal(ks[0], (1, S, H, DN + DR)).astype(dtype)
    lat = jax.random.normal(ks[1], (R + DR, n)).astype(dtype)
    pad = np.arange(S) >= real
    q = jnp.where(pad[None, :, None, None], 0, q)
    lat = lat.at[:, pos0:].set(jnp.where(pad[None, :], 0, lat[:, pos0:]))
    w = (jax.random.normal(ks[2], (R, H, DN + DV)) * R ** -0.5).astype(dtype)
    cache = jnp.full((1, R + DR, M), 7.0, dtype).at[0, :, :pos0].set(
        lat[:, :pos0])
    return q, cache, lat[None, :, pos0:], w[..., :DN], w[..., DN:], lat


def _plain(q, lat, w_uk, w_uv, pos0):
    """softmax(scale q . [W_uk c ‖ k_r]) (W_uv c) over the positions a row
    may see, whole, in float32 at the highest precision."""
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    c, kr = f32(lat[:R]), f32(lat[R:])
    k = jnp.einsum("rm,rhd->mhd", c, f32(w_uk), precision="highest")
    v = jnp.einsum("rm,rhd->mhd", c, f32(w_uv), precision="highest")
    s = (jnp.einsum("shd,mhd->hsm", f32(q[0, ..., :DN]), k,
                    precision="highest")
         + jnp.einsum("shd,dm->hsm", f32(q[0, ..., DN:]), kr,
                      precision="highest")) * SCALE
    ok = np.arange(lat.shape[1])[None, :] <= pos0 + np.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    return np.asarray(jnp.einsum("hsm,mhd->shd", p, v,
                                 precision="highest"))[None]


def _kernel(q, cache, own, w_uk, w_uv, pos0, **kw):
    at = jnp.int32(pos0)
    return ta.latent_tile_attention(
        q, _cache_write(cache, own, at, -1, -3), at, w_uk, w_uv, SCALE,
        interpret=True, **kw)


STARTS = {"at-0": 0, "one-block-in": 256, "several-blocks-in": 768,
          "straddles-a-blocks-edge": 384, "to-the-scratchs-end": M - S}
# float32 holds the arithmetic to the loop's; bf16 is what is served: K and
# V rounded as the loop's einsums round them, the probabilities too
TOL = {jnp.float32: (3e-6, 3e-5), jnp.bfloat16: (1.6e-2, 4e-2)}


@pytest.mark.parametrize("H", [4, 8], ids=["one-group", "two-groups"])
@pytest.mark.parametrize("dtype", list(TOL), ids=["float32", "bf16"])
@pytest.mark.parametrize("pos0", list(STARTS.values()), ids=list(STARTS))
def test_the_kernel_is_the_loop_and_the_plain_softmax(pos0, dtype, H):
    q, cache, own, w_uk, w_uv, lat = _case(pos0, H, dtype)
    assert ta.latent_fits(S, M, H, R, DN, DR, DV)
    assert not sa._latent_tile_kernel_takes(S, M, H, R, DN, DR, DV)  # CPU
    assert ta.latent_group(H) == 4
    kernel = _kernel(q, cache, own, w_uk, w_uv, pos0)
    loop = la.tile_attention(q, cache, own, jnp.int32(pos0), w_uk, w_uv,
                             SCALE)
    assert kernel.dtype == q.dtype and kernel.shape == (1, S, H, DV)
    near, far = TOL[dtype]
    np.testing.assert_allclose(_f32(kernel), _f32(loop), atol=near, rtol=0)
    np.testing.assert_allclose(_f32(kernel), _plain(q, lat, w_uk, w_uv, pos0),
                               atol=far, rtol=0)


@pytest.mark.parametrize("pos0,real", [(0, 1), (512, 100), (768, 255)])
def test_a_last_tile_with_padded_rows(pos0, real):
    """A prompt's last tile: `real` rows of it, zeros behind them (their
    latents too). The real rows attend no padding (it lies past them); the
    padded rows' results are dropped by the caller and only have to be
    numbers."""
    q, cache, own, w_uk, w_uv, lat = _case(pos0, 8, jnp.float32, real=real)
    kernel = np.asarray(_kernel(q, cache, own, w_uk, w_uv, pos0))
    assert np.isfinite(kernel).all()
    np.testing.assert_allclose(
        kernel[:, :real], _plain(q, lat, w_uk, w_uv, pos0)[:, :real],
        atol=3e-5, rtol=0)


@pytest.mark.parametrize("max_rows", [256, 128],
                         ids=["one-query-block", "two-query-blocks"])
@pytest.mark.parametrize("pos0", [0, 384, 768])
def test_no_place_past_the_tiles_last_position_is_read(pos0, max_rows):
    """Every place of the scratch past the key block that holds the tile's
    last position holds NaN (where the tile ends with a block, every place
    past its last position; inside that block the places past it are
    masked, as the loop masks them): no NaN reaches the output. The walk
    is `block_walk`'s, which the other kernel's tests hold to a count over
    the pairs."""
    q, cache, own, w_uk, w_uv, lat = _case(pos0, 8, jnp.float32)
    cache = cache.at[:, :, -(-(pos0 + S) // 256) * 256:].set(np.nan)
    kernel = np.asarray(_kernel(q, cache, own, w_uk, w_uv, pos0,
                                max_rows=max_rows))
    assert np.isfinite(kernel).all()
    np.testing.assert_allclose(kernel, _plain(q, lat, w_uk, w_uv, pos0),
                               atol=3e-5, rtol=0)
    bq, kb = ta.blocks_of(S, M, 4, max_rows)
    first, count = (np.asarray(a) for a in ta.block_walk(
        jnp.int32(pos0), S, M, 0, bq, kb))
    assert (bq, kb) == (max_rows, 256) and not first.any()
    assert list(count) == [(pos0 + (j + 1) * bq - 1) // kb + 1
                           for j in range(S // bq)]


@pytest.mark.parametrize("pos0", [0, 384])
@pytest.mark.parametrize("dtype", list(TOL), ids=["float32", "bf16"])
def test_the_layers_tile_goes_through_the_kernel_where_it_is_taken(
        monkeypatch, pos0, dtype):
    """`latent_attention.tile_attention` as the layer calls it: the scratch
    holds the positions below the tile, the tile's own latents are written
    first, and where the predicate answers as on the chip the tile goes
    through the kernel (made to interpret) and gives what the loop gives;
    a per-row start, two rows or a scratch of another type keep the
    loop."""
    q, cache, own, w_uk, w_uv, lat = _case(pos0, 8, dtype)
    at = jnp.int32(pos0)
    loop = la.tile_attention(q, cache, own, at, w_uk, w_uv, SCALE)
    calls, compiled = [], ta.latent_tile_attention

    def interpreted(*a):
        calls.append(a[0].shape)
        return compiled(*a, interpret=True)

    monkeypatch.setattr(sa, "_latent_tile_kernel_takes", ta.latent_fits)
    monkeypatch.setattr(ta, "latent_tile_attention", interpreted)
    kernel = la.tile_attention(q, cache, own, at, w_uk, w_uv, SCALE)
    assert calls == [q.shape]
    np.testing.assert_allclose(_f32(kernel), _f32(loop), atol=TOL[dtype][0],
                               rtol=0)
    by_row = la.tile_attention(q, cache, own, jnp.reshape(at, (1,)), w_uk,
                               w_uv, SCALE)
    np.testing.assert_array_equal(_f32(by_row), _f32(loop))
    if dtype == jnp.float32:    # (the CPU runs no batched product in bf16)
        two = la.tile_attention(*(jnp.concatenate([a, a]) for a in
                                  (q, cache, own)), at, w_uk, w_uv, SCALE)
        np.testing.assert_array_equal(_f32(two[:1]), _f32(loop))
        la.tile_attention(q, cache.astype(jnp.bfloat16), own, at, w_uk,
                          w_uv, SCALE)
    assert calls == [q.shape]


TAKES = {
    "the-cells": ((1024, 19456, 64, 512, 128, 64, 128), True),
    "a-rotated-key-of-a-whole-lane-tile": ((256, M, 8, 128, 128, 128, 128),
                                           True),
    "a-head-count-of-no-whole-group": ((256, M, 6, 128, 128, 64, 128), True),
    "rows-no-multiple-of-128": ((200, M, 8, 128, 128, 64, 128), False),
    "a-key-block-of-no-whole-lane-tile": ((256, 1280 + 64, 8, 128, 128, 64,
                                           128), False),
    "a-rotated-key-of-32": ((256, M, 8, 128, 128, 32, 128), False),
    "a-rotated-key-of-192": ((256, M, 8, 128, 128, 192, 128), False),
    "latents-of-no-whole-lane-tile": ((256, M, 8, 192, 128, 64, 128), False),
    "an-unrotated-key-of-64": ((256, M, 8, 128, 64, 64, 128), False),
    "values-of-192": ((256, M, 8, 128, 128, 64, 192), False),
}


@pytest.mark.parametrize("shape,takes", list(TAKES.values()), ids=list(TAKES))
def test_what_the_latent_kernel_takes_and_what_it_refuses(shape, takes):
    """The predicate is shapes; on the CPU its twin in
    models/sparse_attention.py refuses everything."""
    assert ta.latent_fits(*shape) is takes
    assert not sa._latent_tile_kernel_takes(*shape)


def test_the_kernel_refuses_two_rows_a_start_a_row_and_a_wrong_scratch():
    q, cache, own, w_uk, w_uv, _ = _case(0, 8, jnp.float32)
    call = functools.partial(ta.latent_tile_attention, interpret=True)
    for bad in (
            lambda: call(jnp.concatenate([q, q]), jnp.concatenate(
                [cache, cache]), jnp.int32(0), w_uk, w_uv, SCALE),
            lambda: call(q, cache, jnp.zeros((1,), jnp.int32), w_uk, w_uv,
                         SCALE),
            lambda: call(q[:, :200], cache, jnp.int32(0), w_uk, w_uv, SCALE),
            lambda: call(q, cache[:, :-16], jnp.int32(0), w_uk, w_uv, SCALE),
            lambda: call(q[..., :DN + 32], cache[:, :R + 32], jnp.int32(0),
                         w_uk, w_uv, SCALE)):
        with pytest.raises(ValueError, match="latent tile kernel takes"):
            bad()


def test_one_pallas_call_a_shape():
    """A stack's layers share one built `pallas_call` (`_latent_call`'s
    cache), as the other kernel's do."""
    args = (S, M, 8, R, DN, DR, DV, 256, 256, SCALE, jnp.dtype(jnp.float32),
            True)
    assert ta._latent_call(*args) is ta._latent_call(*args)
