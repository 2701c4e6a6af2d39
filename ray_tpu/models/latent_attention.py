"""Latent (compressed) attention, the "mla" layer of `mixer_kinds`.

A position keeps ONE entry for all heads: `[c ‖ k_r]`, the layer's normed
latent `c` (`latent_dim` values) and behind it one rotated key `k_r`
(`rope_dim` values) that every head shares; the pool keeps them
`[n_layers, slots, W = latent_dim + rope_dim, positions]`, the positions
last (`transformer.cache_shapes`). With x a block's normed input:

    q        = W_q x               H heads of head_dim = (nope ‖ rope_dim),
                                   RMSNorm a head where `qk_norm`, the rotary
                                   on the second part alone
    [c ‖ k_r] = W_dkv x; c <- RMSNorm(c); k_r <- rope(k_r)     (`latent_rows`)
    k_h      = [W_uk,h c ‖ k_r],  v_h = W_uv,h c                (`kv_up`)
    o        = W_o concat_h softmax_causal(s q_h . k_h) v_h

Two forms compute the same numbers (`tests/test_sarvam_model.py`):

- EXPANDED (`expanded_attention`, a sequence against itself;
  `tile_attention`, a prefill tile against its layer of the scratch): a key
  block's latents are up-projected to K and V inside the loop over key
  blocks and met by q at head_dim / v_head_dim under a running softmax in
  float32. Neither the keys of the whole cache nor a score matrix is ever
  made. A tile of S rows pays the up-projection of a block once for all its
  rows: S * H * 2 * (head_dim + v_head_dim) + 2 * latent_dim * H * (nope +
  v_head_dim) FLOPs a cached position, against S * H * 2 * (2 * latent_dim
  + rope_dim) absorbed: at S = 1,024 and the widths 512 / 128 / 64 / 128,
  58.7 M against 142.6 M. On a TPU one tile (B == 1, one scalar start, q in
  the scratch's type) whose shapes fit it goes through the latent kernel of
  ops/tile_attention.py (`sparse_attention._latent_tile_kernel_takes`): the
  same blocks, the up-projection inside the kernel, once a group of heads,
  and the block's float32 scores `[H, S, blk]`, its probabilities and the
  running softmax's carry held in VMEM where the XLA loop passes them
  through HBM every block. `tile_attention`'s loop is the kernel's
  reference, step for step, and what runs everywhere else.
- ABSORBED (`row_attention`, one decode row a slot against the WHOLE pool
  and the layer's number): q_h . k_h = (W_uk,h^T q_nope,h) . c + q_rope,h .
  k_r and sum_s p_s v_h,s = W_uv,h (sum_s p_s c_s), so the row meets the
  cached rows as they lie, ONE KV "head" of latent_dim + rope_dim for the
  scores whose first latent_dim columns are also the value: one read of a
  block serves both, and nothing is expanded (a row that expanded 9 k
  positions would pay 155 GFLOP a layer). On a TPU, where the shapes fit it
  (`sparse_attention._latent_row_kernel_takes`), the pool is read through
  the latent kernel of ops/decode_attention.py: of each slot the key blocks
  up to ITS last live one and of a slot that holds nothing none, a block's
  float32 scores, its probabilities and the running softmax held in VMEM.
  `row_attention`'s loop, which walks every slot's blocks up to the longest
  slot's last, is the kernel's reference, step for step, and what runs
  everywhere else. The row's own latent is folded into the running softmax
  beside the pool's, as `_row_attention` folds a row's own key and value,
  so the caller's one write after the layers is the only one.

The softmax's scale is head_dim ** -0.5, times YaRN's factor squared where
the rotary is scaled (`softmax_scale`). The cached forward hands the call's
new latents back as the pool keeps them, `[B, W, L]`, for the caller to add
to the pool, as `Attention` hands back an indexer's keys.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ray_tpu.models import sparse_attention as sa
from ray_tpu.models.transformer import (RMSNorm, _cache_write, _join_rows, _p,
                                        _split_rows, rope, yarn_mscale)
from ray_tpu.ops import decode_attention
from ray_tpu.ops import tile_attention as ops_tile_attention


def softmax_scale(cfg) -> float:
    yarn = cfg.rope_yarn
    m = yarn_mscale(yarn.factor, yarn.mscale_all_dim) if yarn else 1.0
    return cfg.head_dim ** -0.5 * m * m


def latent_rows(ckr, norm, positions, cfg):
    """The down-projection's rows `ckr` [B, L, latent_dim + rope_dim] at
    `positions` [B, L] as the cache keeps them and the attention reads
    them: the latent normed (`norm`, the layer's RMSNorm over latent_dim),
    the one key behind it rotated (and not normed)."""
    R = cfg.latent_dim
    kr = rope(ckr[..., None, R:], positions, cfg.rope_theta, cfg.rope_yarn)
    return jnp.concatenate([norm(ckr[..., :R]), kr[..., 0, :]], axis=-1)


def _expand(lat, w_uk, w_uv):
    """Latents as the pool keeps them [B, latent_dim ‖ rope_dim, M] -> each
    head's K without the rotated part [B, M, H, nope], its V
    [B, M, H, v_head_dim], the rotated key [B, rope_dim, M]."""
    R = w_uk.shape[0]
    c = lat[:, :R]
    return (jnp.einsum("brm,rhd->bmhd", c, w_uk),
            jnp.einsum("brm,rhd->bmhd", c, w_uv), lat[:, R:])


def _scores(q, k, kr, scale):
    """q [B, S, H, nope ‖ rope_dim] against a block's K and rotated key ->
    [B, H, 1, S, blk] float32 (a KV head a query head, as `_softmax_step`
    counts them)."""
    Dn = k.shape[-1]
    s = jnp.einsum("bshd,bmhd->bhsm", q[..., :Dn], k,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("bshd,bdm->bhsm", q[..., Dn:], kr,
                     preferred_element_type=jnp.float32)
    return (s * scale)[:, :, None]


def expanded_attention(q, lat, w_uk, w_uv, scale):
    """A sequence against itself, whole: q [B, L, H, head_dim], `lat`
    [B, L, W] its own latents; a plain causal mask over the full score
    matrix (training and the one-shot forward, at sizes where that fits)."""
    L = q.shape[1]
    k, v, kr = _expand(jnp.swapaxes(lat, 1, 2), w_uk, w_uv)
    at = jnp.arange(L)
    s = jnp.where(at[None, :] <= at[:, None],
                  _scores(q, k, kr, scale)[:, :, 0], sa._NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhsm,bmhd->bshd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def tile_attention(q, cache, own, pos0, w_uk, w_uv, scale):
    """A tile q [B, S, H, head_dim] at absolute positions pos0 + 0..S-1
    (pos0 a scalar or [B]) against its layer of the scratch `cache`
    [B, W, M], into which the tile's own latents `own` [B, W, S] are
    written first: causal over absolute positions, blocked over the keys up
    to the tile's last position only, each block's K and V made from its
    latents inside the loop (or inside the Pallas kernel that takes the
    loop's place where `_latent_tile_kernel_takes`: the module's
    docstring) -> [B, S, H, v_head_dim]."""
    B, S, H, D = q.shape
    M, Dv = cache.shape[2], w_uv.shape[-1]
    R, _, Dn = w_uk.shape
    cache = _cache_write(cache, own, pos0, -1, -3)
    if B == 1 and not jnp.ndim(pos0) and q.dtype == cache.dtype \
            and sa._latent_tile_kernel_takes(S, M, H, R, Dn, D - Dn, Dv):
        return ops_tile_attention.latent_tile_attention(
            q, cache, pos0, w_uk, w_uv, scale)
    qpos = jnp.broadcast_to(
        jnp.reshape(pos0, (-1, 1)) + jnp.arange(S)[None, :], (B, S))
    kb = sa._block_of(M)

    def step(i, carry):
        k, v, kr = _expand(jax.lax.dynamic_slice_in_dim(cache, i * kb, kb, 2),
                           w_uk, w_uv)
        mb = (i * kb + jnp.arange(kb)[None, None, :]
              <= qpos[:, :, None])[:, None, None]
        return sa._softmax_step(carry, _scores(q, k, kr, scale), mb, v)

    m0 = jnp.full((B, H, 1, S), sa._NEG, jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0, jnp.minimum((jnp.max(qpos) + kb) // kb, M // kb), step,
        (m0, jnp.zeros_like(m0), jnp.zeros((B, H, 1, S, Dv), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out[:, :, 0].transpose(0, 2, 1, 3).astype(q.dtype)


def row_attention(q, own, pool, layer, lens, w_uk, w_uv, scale):
    """One row a slot, absorbed: q [B, 1, H, head_dim] at position lens[b]
    against layer `layer` (traced) of the pool [n_layers, B, W, M], which
    holds the positions below lens[b] and is read where it lies: through
    the Pallas kernel where `_latent_row_kernel_takes` (the module's
    docstring), each slot's key blocks up to its own last; else in key
    blocks up to the LONGEST live slot's last, every slot's block in each
    step (`sparse_attention.latent_positions_read` counts either); `own`
    [B, 1, W], the row's own latent as projected, beside them
    -> [B, 1, H, v_head_dim]."""
    B, _, H, _ = q.shape
    W, M = pool.shape[2:]
    R, Dn = w_uk.shape[0], w_uk.shape[-1]
    lens = jnp.broadcast_to(jnp.reshape(lens, (-1,)), (B,))
    # W_uk into the query, once a row: [B, H, W]
    qa = jnp.concatenate([jnp.einsum("bhd,rhd->bhr", q[:, 0, :, :Dn], w_uk),
                          q[:, 0, :, Dn:]], axis=-1)
    block = decode_attention.block_of(M)

    def fold(carry, rows, ok):
        """Latents [B, W, n] under ok [B, n] into the running softmax."""
        m, l, acc = carry
        s = jnp.einsum("bhw,bwn->bhn", qa, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok[:, None], s, sa._NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(ok[:, None], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=-1),
                acc * alpha[..., None] + jnp.einsum(
                    "bhn,brn->bhr", p.astype(rows.dtype), rows[:, :R],
                    preferred_element_type=jnp.float32))

    def step(i, carry):
        rows = jax.lax.dynamic_slice(
            pool, (layer, 0, 0, i * block), (1, B, W, block))[0]
        return fold(carry, rows, i * block + jnp.arange(block)[None, :]
                    < lens[:, None])

    if sa._latent_row_kernel_takes(M, H, W, R):
        carry = decode_attention.latent_pool_decode_attention(
            qa, pool, layer, lens, R, scale)
    else:
        carry = jax.lax.fori_loop(
            0, (jnp.max(lens) + block - 1) // block, step,
            (jnp.full((B, H), sa._NEG, jnp.float32),
             jnp.zeros((B, H), jnp.float32),
             jnp.zeros((B, H, R), jnp.float32)))
    _, l, acc = fold(carry, jnp.swapaxes(own, 1, 2).astype(pool.dtype),
                     jnp.ones((B, 1), bool))
    mean = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return jnp.einsum("bhr,rhd->bhd", mean, w_uv)[:, None]


class LatentAttention(nn.Module):
    """An "mla" layer's mixer (the module's docstring). Its one cache is
    the pool "lat" (`transformer.cache_shapes`); `cache` and `slots` are
    what `Attention` takes: ((the layer of the scratch,), idx) for a tile,
    ((the WHOLE pool,), idx, the layer's number) for one row a slot, and
    `slots` = ((the slots' whole pool,), lengths, on, number) where the
    sequence [1, T + S] is a tile followed by one decode row a slot. The
    projections run once over all rows; the two forms name themselves to
    the trace, `mla_attend` and `mla_row`, each with its norm and rotary of
    the latents it makes and its up-projections inside."""
    cfg: Any

    @nn.compact
    def __call__(self, x, positions, cache=None, slots=None):
        cfg = self.cfg
        E = x.shape[-1]
        H, D, R, Dr, Dv = (cfg.n_heads, cfg.head_dim, cfg.latent_dim,
                           cfg.rope_dim, cfg.v_head_dim)
        dense = lambda feats, axes, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=_p(nn.initializers.lecun_normal(), *axes))
        q = dense((H, D), ("embed", "heads", "head_dim"), "q")(x)
        if cfg.qk_norm:
            q = RMSNorm(cfg.norm_eps, cfg.dtype, "head_dim",
                        name="q_norm")(q)
        q = jnp.concatenate([q[..., :D - Dr], rope(
            q[..., D - Dr:], positions, cfg.rope_theta, cfg.rope_yarn)], -1)
        ckr = dense(R + Dr, ("embed", None), "kv_down")(x)
        norm = RMSNorm(cfg.norm_eps, cfg.dtype, None, name="kv_norm")
        w_ukv = self.param(
            "kv_up", _p(nn.initializers.lecun_normal(in_axis=0,
                                                     out_axis=(1, 2)),
                        None, "heads", "head_dim"),
            (R, H, D - Dr + Dv), cfg.param_dtype).astype(cfg.dtype)
        up = (w_ukv[..., :D - Dr], w_ukv[..., D - Dr:],
              softmax_scale(cfg))
        proj = nn.DenseGeneral(
            E, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o",
            kernel_init=_p(nn.initializers.lecun_normal(),
                           "heads", "head_dim", "embed"))
        if cache is None:
            return proj(expanded_attention(
                q, latent_rows(ckr, norm, positions, cfg), *up))
        (layer_or_pool,), idx, *number = cache

        def tile(q, ckr, positions):
            with jax.named_scope("mla_attend"):
                lat = jnp.swapaxes(latent_rows(ckr, norm, positions, cfg),
                                   1, 2)
                return tile_attention(q, layer_or_pool, lat, idx, *up), lat

        def row(q, ckr, positions, pool, number, lens):
            with jax.named_scope("mla_row"):
                lat = latent_rows(ckr, norm, positions, cfg)
                return (row_attention(q, lat, pool, number, lens, *up),
                        jnp.swapaxes(lat, 1, 2))

        if slots is not None:
            (pool,), lens, _, at = slots
            n = len(lens)
            (q, qr), (ckr, ckr_r) = (_split_rows(a, n) for a in (q, ckr))
            out, lat = tile(q, ckr, positions[:, :-n])
            # not under a `cond` on `on` (as in `Attention._in_place`)
            out_r, lat_r = row(qr, ckr_r, positions[:, -n:].T, pool, at, lens)
            return proj(_join_rows(out, out_r)), ((lat,), (lat_r,))
        if number:
            out, lat = row(q, ckr, positions, layer_or_pool, *number, idx)
        else:
            out, lat = tile(q, ckr, positions)
        return proj(out), (lat,)
