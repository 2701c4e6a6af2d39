"""Lightning (linear) attention: a recurrent state in place of a cache of
keys and values (Lightning Attention-2's recurrence with a fixed decay a
head).

For each head h, with q_t, k_t, v_t [D] and a state S [D, D] in float32:

    S_t = lambda_h S_{t-1} + k_t v_t^T
    o_t = q_t^T S_t / sqrt(D)
    lambda_h = exp(-s_h),  s_h = 2^(-8 h / H),  h = 1..H

Two forms of the same mathematics, chosen by the caller from what it holds
(models/transformer.py `LightningAttention`):

- `lightning_scan`: a sequence (a prefill tile) that starts from a state
  and hands one on. Chunk by chunk: inside a chunk the causal products
  q_t . k_u weighted by lambda^(t-u), between chunks through the state. A
  row no request owns (`real` False: a tile's padded tail) neither decays
  the state nor adds to it, so the state handed on is the state after the
  request's own rows and nothing else.
- `lightning_step`: one row a slot, the recurrence itself, elementwise in
  float32 (a matrix-vector product a head: nothing for the MXU).

Where chunks or tiles fall changes the result by float32 rounding only:
every decay is computed as exp of a DIFFERENCE of cumulated exponents, so
no factor grows (head 1 loses exp(-0.84) a token, and exp(+0.84 x 127)
overflows nothing here because it is never formed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 128          # rows a chunk of `lightning_scan` holds
_HIGHEST = jax.lax.Precision.HIGHEST


def decay_rates(n_heads: int):
    """s_h [H] float32: head h (1-based) decays by exp(-s_h) a token."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / n_heads)


def lightning_step(q, k, v, state):
    """One row a slot: q, k, v [B, 1, H, D], state [B, H, D, D] float32
    -> (o [B, 1, H, D] in q's type, the new state). The row sees itself:
    the state is advanced first."""
    B, _, H, D = q.shape
    with jax.named_scope("lightning_step"):
        lam = jnp.exp(-decay_rates(H))[None, :, None, None]
        k32, v32 = k[:, 0].astype(jnp.float32), v[:, 0].astype(jnp.float32)
        state = lam * state + k32[..., :, None] * v32[..., None, :]
        o = jnp.sum(q[:, 0].astype(jnp.float32)[..., :, None] * state,
                    axis=-2) * D ** -0.5
        return o[:, None].astype(q.dtype), state


def lightning_scan(q, k, v, state, real=None, chunk: int = CHUNK):
    """A sequence q, k, v [B, T, H, D] from `state` [B, H, D, D] float32
    -> (o [B, T, H, D] in q's type, the state after the sequence's real
    rows). `real` [B, T] bool: the rows a request owns (absent: all)."""
    B, T, H, D = q.shape
    C = min(chunk, T)
    pad = -T % C
    if real is None:
        real = jnp.ones((B, T), bool)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        real = jnp.pad(real, ((0, 0), (0, pad)))
    N = (T + pad) // C
    scale = D ** -0.5
    # [N, B, C, ..]: the chunks are the scan's axis
    qs, ks, vs = (a.reshape(B, N, C, H, D).swapaxes(0, 1) for a in (q, k, v))
    rs = real.reshape(B, N, C).swapaxes(0, 1)
    s = decay_rates(H)
    causal = jnp.tril(jnp.ones((C, C), bool))

    def body(S, xs):
        qn, kn, vn, rn = xs
        r32 = rn.astype(jnp.float32)
        # b[t]: the exponent cumulated over the chunk's real rows to t
        b = -jnp.cumsum(r32, axis=1)[:, :, None] * s              # [B,C,H]
        bh = b.transpose(0, 2, 1)                                 # [B,H,C]
        # a row that is not real adds nothing: its column is zeroed
        A = jnp.where(causal, jnp.exp(jnp.where(
            causal, bh[..., :, None] - bh[..., None, :], 0.0)), 0.0) \
            * r32[:, None, None, :]
        w = jnp.einsum("bthd,buhd->bhtu", qn, kn,
                       preferred_element_type=jnp.float32) * A
        o = jnp.einsum("bhtu,buhd->bthd", w.astype(vn.dtype), vn,
                       preferred_element_type=jnp.float32)
        o = o + jnp.einsum("bthd,bhde->bthe", qn.astype(jnp.float32), S,
                           precision=_HIGHEST) * jnp.exp(b)[..., None]
        left = (jnp.exp(b[:, -1:] - b) * r32[..., None])[..., None]
        S = jnp.exp(bh[..., -1])[..., None, None] * S + jnp.einsum(
            "buhd,buhe->bhde", kn.astype(jnp.float32) * left,
            vn.astype(jnp.float32), precision=_HIGHEST)
        return S, (o * scale).astype(q.dtype)

    with jax.named_scope("lightning_scan"):
        state, out = jax.lax.scan(body, state, (qs, ks, vs, rs))
        out = out.swapaxes(0, 1).reshape(B, T + pad, H, D)
        return out[:, :T], state
