"""The mixers of a decoder whose second half keeps no cache: differential
attention (the "win", "att" and "xat" layers of a model with `diff_attn`),
the Mamba-1 mixer as a layer of its own ("s6") and the gated memory unit
("gmu"). Phi-4-mini-flash's "SambaY" (arXiv:2507.06607) is such a stack.

DIFFERENTIAL ATTENTION. The H query heads of `head_dim` are H / 2 pairs
(q_2i, q_2i+1), the Hkv KV heads Hkv / 2 pairs; pair i reads KV pair
j = i // (H / Hkv). With P(a, b) = softmax_causal(q_a k_b^T / sqrt(d)) and
V_j = [v_2j ‖ v_2j+1] (2 d wide):

    o_i = (1 - l0) RMSNorm_2d( P(2i, 2j) V_j - l P(2i+1, 2j+1) V_j )
    l   = exp(lq1 . lk1) - exp(lq2 . lk2) + l0,  l0 = 0.8 - 0.6 exp(-0.3 depth)

Both maps of a pair are ORDINARY grouped-query attention at twice the head
size: K and V are kept by PAIR, [.., Hkv / 2, 2 d] (`cache_shapes`; at d =
64 a pair fills the 128 lanes a head of 64 would half fill), query head 2i
is laid in a pair's first d lanes and 2i + 1 in its last d, zeros beside it
(`paired`), so that q'_2i . [k_2j ‖ k_2j+1] = q_2i . k_2j and the H padded
heads read the Hkv / 2 pairs in groups of 2 H / Hkv, as they lie. So a tile
goes through `transformer._tile_attention` unchanged (key blocks, a running
softmax in float32 a map, the Pallas kernel where the pair's shapes fit it)
and a decode row through `row_attention` below, the same blocks by an XLA
loop, and `combine` takes the difference, the norm and the scale of the two
results. The zeros cost the score product twice its useful FLOPs (2 d for
d) and the value product nothing; no lane is sliced. The projections have
biases. There is no positional encoding: the "s6" layers carry position.

An "xat" layer projects a query and nothing else: it attends the K and V of
the stack's LAST "att" layer before it, whose pools it reads and does not
write (`TransformerLM._decode` hands it that layer's scratch, already
written with the tile's rows, or the whole pools and that layer's number,
and `shared["kv_rows"]`, the decode rows' own key and value).

The "s6" layer (`S6Mixer`) is models/ssm.py's `s6_scan` / `s6_step` between
its projections; the LAST one's output before the gate is the memory
`shared["mem"]` that every "gmu" layer (`GatedMemory`) gates and projects.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ray_tpu.models.transformer import (KIND_READS, _join_rows, _p,
                                        _split_rows, _tile_attention)


def lambda_init(depth: int) -> float:
    """l0 of the layer `depth` (0-based) of the stack."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def paired(q):
    """q [B, L, H, d] -> [B, L, H, 2 d]: an even head in the first d lanes,
    an odd one in the last d, zeros beside it; times sqrt(2), so that the
    attention's 1 / sqrt(2 d) is the pair's 1 / sqrt(d)."""
    B, L, H, d = q.shape
    q = (q.astype(jnp.float32) * math.sqrt(2.0)).astype(q.dtype)
    q = q.reshape(B, L, H // 2, 2, 1, d)
    lane = jnp.eye(2, dtype=q.dtype)[:, :, None]           # [2, 2, 1]
    return (q * lane).reshape(B, L, H, 2 * d)


def combine(a, lam, lam0: float, scale, eps: float):
    """The two maps' results a [B, L, H, 2 d] (map 1 of pair i at head 2i,
    map 2 at 2i + 1) -> the pairs' outputs [B, L, H / 2, 2 d] in a's type:
    the difference under `lam`, RMSNorm with the learned `scale` [2 d],
    times 1 - lam0. In float32."""
    B, L, H, W = a.shape
    a32 = a.astype(jnp.float32).reshape(B, L, H // 2, 2, W)
    d = a32[..., 0, :] - lam * a32[..., 1, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps)
    return (d * scale.astype(jnp.float32) * (1.0 - lam0)).astype(a.dtype)


def row_attention(q, k_new, v_new, k_pool, v_pool, layer, lens,
                  window: int = 0):
    """A decode row of a model with `diff_attn`, as
    `transformer._row_attention` is the other models': one row a slot, q
    [B, 1, H, D] at position lens[b], against layer `layer` of the pools
    [n, B, M, Hkv, D] READ WHERE THEY LIE and as they lie, five axes: a
    loop over the key blocks up to the longest slot's last, each block
    [B, blk, Hkv, D] sliced out of the pool, a running softmax in float32,
    the row's own key and value folded in last. `window`: the pools are
    rings, as `_row_attention` reads them. (No Pallas kernel: the pool
    kernel of ops/decode_attention.py reads a pool as the matrix
    [M * Hkv, D], and where a position's pairs are no whole sublane tile,
    as 10 are not, the chip keeps the pool positions-minor and that view
    is a COPY of the whole pool a step, 2 x 1.0 GB for K and V of 16
    slots of 12,288: read off the program compiled for a described v5e,
    PR 53. `decode_rows_read` counts this loop.)"""
    from ray_tpu.models import sparse_attention as sa
    from ray_tpu.ops import decode_attention
    B, _, H, D = q.shape
    M, Hkv = k_pool.shape[2:4]
    lens = jnp.broadcast_to(jnp.reshape(lens, (-1,)), (B,))
    kb = decode_attention.block_of(M)
    qg = q.reshape(B, 1, Hkv, H // Hkv, D)
    held = jnp.minimum(lens, M)             # places that hold a position

    def seen(place):
        """[B, blk]: whether the row attends what `place` [blk] holds."""
        if not window:
            return place[None, :] < lens[:, None]
        # how far behind the newest position kept (lens - 1) the place's is
        back = (lens[:, None] - 1 - place[None, :]) % M
        return (back <= window - 2) & (back < lens[:, None])

    def scores(kblk):
        return jnp.einsum("bshgd,bmhd->bhgsm", qg, kblk,
                          preferred_element_type=jnp.float32) * D ** -0.5

    def step(i, carry):
        kblk, vblk = (jax.lax.dynamic_slice(
            p, (layer, 0, i * kb, 0, 0), (1, B, kb, Hkv, D))[0]
            for p in (k_pool, v_pool))
        mb = seen(i * kb + jnp.arange(kb))[:, None, None, None, :]
        return sa._softmax_step(carry, scores(kblk), mb, vblk)

    m0 = jnp.full((B, Hkv, H // Hkv, 1), -1e30, jnp.float32)
    carry = jax.lax.fori_loop(
        0, (jnp.max(held) + kb - 1) // kb, step,
        (m0, jnp.zeros_like(m0),
         jnp.zeros((B, Hkv, H // Hkv, 1, D), jnp.float32)))
    _, l, acc = sa._softmax_step(carry, scores(k_new), True, v_new)
    # float32, as the running softmax kept it (`combine` takes the pairs'
    # difference in float32)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, D)


class DiffAttention(nn.Module):
    """A "win", "att" or "xat" layer of a model with `diff_attn` (the
    module's docstring). `cache` and `slots` are what `Attention` takes;
    `shared`: what the stack hands on beside the hidden state (an "att"
    layer without a cache leaves its K and V there, an "xat" layer reads
    them). `picked`: the tile's rows are not consecutive (the rows the
    caller samples, gathered before this layer): an "xat" layer then
    attends each at its own position. The forms name themselves to the
    trace, `diff_attend` (a tile) and `diff_row` (decode rows)."""
    cfg: Any
    kind: str
    depth: int
    picked: bool = False

    @nn.compact
    def __call__(self, x, positions, cache=None, slots=None, shared=None):
        cfg = self.cfg
        B, L, E = x.shape
        H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        xat = self.kind in KIND_READS
        # (a layer that reads a window layer's ring attends its window)
        window = cfg.window \
            if KIND_READS.get(self.kind, self.kind) == "win" else 0
        dense = lambda feats, axes, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=True, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=_p(nn.initializers.lecun_normal(), *axes))
        q = paired(dense((H, D), ("embed", "heads", "head_dim"), "q")(x))
        k = v = None
        if not xat:                     # by pair, as the caches keep them
            k, v = (dense((Hkv, D), ("embed", "kv_heads", "head_dim"),
                          name)(x).reshape(B, L, Hkv // 2, 2 * D)
                    for name in ("k", "v"))
        lq1, lk1, lq2, lk2 = (
            self.param(name, _p(nn.initializers.normal(0.1), None), (D,),
                       jnp.float32)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        lam0 = lambda_init(self.depth)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
            + lam0
        subln = self.param("subln", _p(nn.initializers.ones, None),
                           (2 * D,), jnp.float32)
        proj = nn.DenseGeneral(
            E, axis=(-2, -1), use_bias=True, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o",
            kernel_init=_p(nn.initializers.lecun_normal(),
                           "heads", "head_dim", "embed"))

        def out_of(a):
            return proj(combine(a, lam, lam0, subln, cfg.norm_eps))

        from ray_tpu.models import sparse_attention as sa
        if cache is None:
            if xat:
                k, v = shared["kv"]
            at = jnp.arange(L)
            mask = at[None, :] <= at[:, None]
            if window:
                mask &= at[None, :] > at[:, None] - window
            with jax.named_scope("diff_attend"):
                out = sa.masked_attention(
                    q, k, v, jnp.broadcast_to(mask, (B, L, L)))
            return out_of(out), (k, v)
        (k_layer, v_layer), idx, *number = cache

        def tile(q, k, v, positions):
            with jax.named_scope("diff_attend"):
                if not xat:
                    return _tile_attention(q, k_layer, v_layer, idx, window,
                                           own=(k, v))
                # the scratch holds the tile's rows already
                if not self.picked:
                    return _tile_attention(q, k_layer, v_layer, idx, window)
                return jnp.concatenate([
                    _tile_attention(q[:, r:r + 1], k_layer, v_layer,
                                    positions[0, r], window)
                    for r in range(q.shape[1])], axis=1)

        def row(q, k, v, k_pool, v_pool, number, lens):
            with jax.named_scope("diff_row"):
                return row_attention(q, k, v, k_pool, v_pool, number, lens,
                                     window)

        if slots is not None:
            (k_pool, v_pool), lens, _, at = slots
            n = len(lens)
            q, qr = _split_rows(q, n)
            if xat:
                kr, vr = shared["kv_rows"]
                new = ((), ())
            else:
                (k, kr), (v, vr) = (_split_rows(a, n) for a in (k, v))
                new = ((k, v), (kr, vr))
            out = _join_rows(tile(q, k, v, positions[:, :L - n]),
                             row(qr, kr, vr, k_pool, v_pool, at, lens))
            return out_of(out), new
        if number:
            if xat:
                k, v = shared["kv_rows"]
            out = row(q, k, v, k_layer, v_layer, *number, idx)
        else:
            out = tile(q, k, v, positions)
        return out_of(out), (() if xat else (k, v))


def _gate(m, z):
    """M * silu(z), in float32, in M's type."""
    return (m.astype(jnp.float32)
            * nn.silu(z.astype(jnp.float32))).astype(m.dtype)


class S6Mixer(nn.Module):
    """An "s6" layer's mixer (models/ssm.py): [x ‖ z] = W_in m; x <-
    silu(conv(x)), depthwise and causal over x's `s6_inner` channels, with
    bias; [delta ‖ B ‖ C] = W_x x; dt = softplus(W_dt delta + b_dt), a step
    a CHANNEL; A = -exp(A_log) [N, inner]; the recurrence; the output
    W_out (M * silu(z)). Its caches are two states with no position: "s"
    [B, N, inner] float32 and "c" [B, K - 1, inner] float32, the last
    K - 1 real rows of the convolution's input; a call takes both in and
    hands both back WHOLE. -> (the output, the new states or None, M: the
    recurrence's result BEFORE the gate, in the activations' type). `real`
    [B, L] bool: the rows a request owns."""
    cfg: Any

    @nn.compact
    def __call__(self, x, cache=None, slots=None, real=None):
        from ray_tpu.models import ssm
        cfg = self.cfg
        B, L, E = x.shape
        I, N, K, R = cfg.s6_inner, cfg.s6_state, cfg.s6_conv, cfg.s6_dt_rank
        dense = lambda feats, name, axes, bias=False: nn.DenseGeneral(  # noqa: E731,E501
            feats, axis=-1, use_bias=bias, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=_p(nn.initializers.lecun_normal(), *axes))
        vec = lambda name, init, shape: self.param(  # noqa: E731
            name, _p(init, *(None,) * len(shape)), shape, cfg.param_dtype)
        xs = dense(I, "in_x", ("embed", "mlp"))(x)
        z = dense(I, "in_z", ("embed", "mlp"))(x)
        conv_w = vec("conv_w", nn.initializers.lecun_normal(), (K, I))
        conv_b = vec("conv_b", nn.initializers.zeros, (I,))
        x_proj = dense(R + 2 * N, "x_proj", ("mlp", None))
        dt_proj = dense(I, "dt_proj", (None, "mlp"), bias=True)
        A = -jnp.exp(vec("A_log", nn.initializers.zeros, (N, I))
                     .astype(jnp.float32))
        D = vec("D", nn.initializers.ones, (I,))

        def inputs(xs, tail, real):
            y, tail = ssm.causal_conv(xs, tail, conv_w, conv_b, real)
            y = nn.silu(y).astype(cfg.dtype)
            dbc = x_proj(y)
            dt = jax.nn.softplus(dt_proj(dbc[..., :R]).astype(jnp.float32))
            return y, dt, dbc[..., R:R + N], dbc[..., R + N:], tail

        def scan(xs, state, tail, real):
            y, dt, bm, cm, tail = inputs(xs, tail, real)
            m, state = ssm.s6_scan(y, dt, A, bm, cm, D, state, real)
            return m, state, tail

        def step(xs, state, tail, real):
            y, dt, bm, cm, tail = inputs(xs, tail, real)
            m, state = ssm.s6_step(y, dt, A, bm, cm, D, state,
                                   None if real is None else real[:, 0])
            return m, state, tail

        new = None
        if cache is None:
            m, _, _ = scan(xs, jnp.zeros((B, N, I), jnp.float32),
                           jnp.zeros((B, K - 1, I), jnp.float32), real)
        else:
            (state, tail), _ = cache
            if slots is not None:
                (states, tails), lens, _ = slots
                n = len(lens)
                xs, xs_r = _split_rows(xs, n)
                tile_real, rows_real = (None, None) if real is None else (
                    real[:, :L - n], real[0, L - n:, None])
                m, state, tail = scan(xs, state, tail, tile_real)
                # always computed (no `cond`): a row no request owns
                # leaves its states as they were
                m_r, states, tails = step(xs_r, states, tails, rows_real)
                m = _join_rows(m, m_r)
                new = ((state, tail), (states, tails))
            else:
                m, state, tail = (scan if L > 1 else step)(
                    xs, state, tail, real)
                new = (state, tail)
        return dense(E, "out", ("mlp", "embed"))(_gate(m, z)), new, m


class GatedMemory(nn.Module):
    """A "gmu" layer's mixer: W_2 (silu(W_1 m) * M), M the memory the last
    "s6" layer left at the same position. No cache, no state."""
    cfg: Any

    @nn.compact
    def __call__(self, x, mem):
        cfg = self.cfg
        dense = lambda feats, name, axes: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=_p(nn.initializers.lecun_normal(), *axes))
        with jax.named_scope("gmu"):
            g = nn.silu(dense(mem.shape[-1], "in", ("embed", "mlp"))(x))
            return dense(x.shape[-1], "out", ("mlp", "embed"))(g * mem)
