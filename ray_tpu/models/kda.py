"""Delta-rule linear attention with a decay a CHANNEL (Kimi Delta Attention,
arXiv:2510.26692), the "kda" layer of `mixer_kinds`: a recurrent state in
place of a cache of keys and values, corrected by what it already holds.

For each head, with q_t, k_t [K] (unit vectors, q times K^-1/2), v_t [V], a
log-decay g_t [K] <= 0 a channel of the key, a step beta_t in (0, 1), and a
state S [K, V] in float32:

    S' = Diag(exp(g_t)) S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)          what the state does not yet say
    S_t = S' + k_t u_t^T                   (the delta rule)
    o_t = S_t^T q_t

that is S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
v_t^T. models/linear_attention.py's recurrence has one fixed decay a head
and adds k v^T whatever the state holds; here the decay depends on the
input and differs by channel, and the row's value is first lessened by what
the state returns for its key.

Two forms of the same mathematics, chosen by the caller from what it holds
(`KimiDeltaAttention`):

- `kda_step`: one row a slot, the recurrence itself, elementwise in float32.
- `kda_scan`: a sequence (a prefill tile) that starts from a state and hands
  one on, in chunks of C rows. With G_t the chunk's cumulated g a channel,
  for a chunk from S_0:

      A_ti = beta_t sum_d k_td k_id exp(G_td - G_id)              (i < t)
      U = (I + A)^-1 Diag(beta) V,   W = (I + A)^-1 Diag(beta) (K . e^G)
      u = U - W S_0
      o_t = S_0^T (q_t . e^G_t) + sum_{i<=t} (sum_d q_td k_id
                                              exp(G_td - G_id)) u_i
      S_C = Diag(e^G_C) S_0 + sum_i (k_i . e^(G_C - G_i)) u_i^T

  A, U, W and the products q . k are made for ALL chunks of the tile at
  once (they need no state); only the three lines that touch S run chunk
  after chunk (`lax.scan`), four matrix products a chunk. (I + A)^-1 is
  forward substitution (`_unit_lower_inverse`: row by row inside diagonal
  blocks of `sub` rows, the blocks joined two and two), exact for any keys:
  the series sum (-A)^j, which squares its way there in six products, is
  lost to cancellation where neighbouring keys are alike and decay little.

  No decay is formed as exp(-G) alone over a chunk (over 64 rows -G reaches
  320 at the lower bound of -5 and float32 ends at 88). The pairwise weights
  exp(G_td - G_id) of a row t of block a (blocks of `sub` rows) are the
  product of two factors about the block's first row r: exp(G_t - G_r) on
  the row, <= 1, and exp(G_r - G_i) on the column, <= 1 for a row i of an
  earlier block and, inside block a itself, at most exp(floor x (sub - 1)):
  under the gate's lower bound of -5 and 16 rows a block, exp(75), which
  float32 holds (`TransformerConfig` refuses a floor and a `kda_sub` whose
  product passes SUB_SPAN = 80). Every term of a sum is then a product of
  two finite numbers whose own product is <= 1, and the weights of a whole
  chunk are ONE matrix product of [rows, K] arrays against [C / sub, C, K]
  column factors, 4 MB a chunk. By count (PERF.md section 6, PR 58): the
  whole [C, C, K] float32 array of a chunk of 64 is 67 MB a layer a chunk
  over 32 heads of 128, 3 GB a layer a tile; the differences themselves
  inside the 16 x 16 diagonal blocks alone (the first form built) were 268
  MB a layer a tile where XLA did not fuse them, 0.8 of 2.3 ms a layer.

A row no request owns (`real` False: a tile's padded tail, a dead slot's row
behind a tile) has g = 0 and beta = 0: it neither decays the state nor adds
to it. The state, the norms of q and k, g, beta and every product that
touches the state are float32 at precision HIGHEST.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ray_tpu.models import ssm
from ray_tpu.models.transformer import _join_rows, _p, _split_rows

_HIGHEST = jax.lax.Precision.HIGHEST


def kda_gate(a, A_log, bias, floor: float):
    """The log-decay a channel: a [.., H, K] (the gate's projection), A_log
    [H], bias [H, K] -> g = floor * sigmoid(exp(A_log) * (a + bias)), in
    (floor, 0), float32 (`floor` < 0: the lower bound)."""
    a = a.astype(jnp.float32) + bias.astype(jnp.float32)
    return floor * jax.nn.sigmoid(
        jnp.exp(A_log.astype(jnp.float32))[:, None] * a)


def unit(x, scale: float = 1.0):
    """x [.., K] -> x / ||x|| * scale, float32."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
                * scale)


def conv_row(x, tail, w, b, real=None, scope: str = "kda_conv"):
    """`ssm.causal_conv` for ONE row a slot, x [B, 1, C]: the taps over the
    tail and the row, and the tail moved on by the row where it is real
    (`real` [B, 1] bool) and left as it was where not. (The general form
    takes each slot's last rows by a gather: a third of a millisecond a
    layer at 32 slots of 12,288 channels, my chip run, PR 58.)"""
    with jax.named_scope(scope):
        xin = jnp.concatenate([tail.astype(jnp.float32),
                               x.astype(jnp.float32)], axis=1)
        y = jnp.sum(w.astype(jnp.float32)[None] * xin, axis=1, keepdims=True)
        if b is not None:
            y = y + b.astype(jnp.float32)
        new = xin[:, 1:]
        if real is not None:
            new = jnp.where(real[:, :, None], new, xin[:, :-1])
        return y, new.astype(tail.dtype)


def gated_norm(o, scale, gate, eps: float):
    """A head's output o [.., H, V] float32 under an RMSNorm over V (one
    `scale` [V] for all heads), times its head's gate [.., H] in (0, 1)."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * scale
    return o * gate[..., None]


def kda_step(q, k, v, g, beta, state, real=None):
    """One row a slot: q, k [B, 1, H, K] float32 (normed), v [B, 1, H, V],
    g [B, 1, H, K] float32 (<= 0), beta [B, 1, H] float32, state
    [B, H, K, V] float32 -> (o [B, 1, H, V] float32, the new state). The row
    sees itself: the state is advanced first. `real` [B] bool: a row that is
    not leaves its state as it was (g = 0, beta = 0)."""
    with jax.named_scope("kda_step"):
        q, k, g, beta = q[:, 0], k[:, 0], g[:, 0], beta[:, 0]
        v = v[:, 0].astype(jnp.float32)
        if real is not None:
            g = g * real[:, None, None]
            beta = beta * real[:, None]
        held = jnp.exp(g)[..., None] * state
        u = beta[..., None] * (v - jnp.sum(held * k[..., None], axis=-2))
        new = held + k[..., None] * u[..., None, :]
        o = jnp.sum(new * q[..., None], axis=-2)
        return o[:, None], new


def _unit_lower_inverse(A, sub: int):
    """(I + A)^-1 for A [.., C, C] strictly lower triangular, float32, by
    forward substitution: row r of the inverse is e_r - sum_{i<r} A_ri
    row_i, inside each diagonal block of `sub` rows (the rows unrolled,
    every block of every matrix at once); then neighbouring blocks are
    joined, [[X, 0], [-Y A_21 X, Y]], until one is left (C / sub a power of
    two)."""
    C = A.shape[-1]
    n = C // sub
    lead = A.shape[:-2]
    blocks = A.reshape(lead + (n, sub, n, sub))
    diag = jnp.stack([blocks[..., a, :, a, :] for a in range(n)], -3)
    eye = jnp.eye(sub, dtype=A.dtype)
    rows = []
    for r in range(sub):
        row = jnp.broadcast_to(eye[r], lead + (n, sub))
        if r:
            row = row - jnp.einsum("...i,...ij->...j", diag[..., r, :r],
                                   jnp.stack(rows, -2), precision=_HIGHEST)
        rows.append(row)
    inv = jnp.stack(rows, -2)                       # [.., n, sub, sub]
    size = sub
    while n > 1:
        n //= 2
        whole = A.reshape(lead + (n, 2, size, n, 2, size))
        low = jnp.stack([whole[..., a, 1, :, a, 0, :] for a in range(n)],
                        -3)                         # A_21 of each pair
        pair = inv.reshape(lead + (n, 2, size, size))
        X, Y = pair[..., 0, :, :], pair[..., 1, :, :]
        Z = -jnp.einsum("...ij,...jk,...kl->...il", Y, low, X,
                        precision=_HIGHEST)
        inv = jnp.concatenate([
            jnp.concatenate([X, jnp.zeros_like(X)], -1),
            jnp.concatenate([Z, Y], -1)], -2)       # [.., n, 2 size, 2 size]
        size *= 2
    return inv[..., 0, :, :]


SUB_SPAN = 80.0      # the most a block of `sub` rows may decay, in nats


def _pair_weights(q, k, G, sub: int):
    """(A_ti = sum_d k_td k_id exp(G_td - G_id) for i < t, Q_ti = sum_d q_td
    k_id exp(G_td - G_id) for i <= t), 0 elsewhere: q, k, G [B, N, C, H, K]
    float32 -> two [B, N, H, C, C]. ONE matrix product for both (the rows of
    k and of q side by side) and for a whole chunk: the weights of a row t
    of block a are two factors about the block's first row r, exp(G_t -
    G_r) on the row, which is <= 1, and exp(G_r - G_i) on the column, which
    is <= 1 for a row i of an earlier block and at most exp(SUB_SPAN) for
    one of block a itself (the module's docstring); a column behind the
    block's end has none, and what lies above the diagonal is cut after the
    product."""
    B, N, C, H, K = k.shape
    n = C // sub
    by = lambda a: a.reshape((B, N, n, sub) + a.shape[3:])   # noqa: E731
    xs = by(jnp.stack([k, q], axis=3))                       # [B,N,a,t,2,H,K]
    Gs = by(G)
    ref = Gs[:, :, :, 0]                                     # [B,N,a,H,K]
    down = jnp.exp(Gs - ref[:, :, :, None])                  # <= 1
    upto = (jnp.arange(C)[None, :]
            < ((jnp.arange(n) + 1) * sub)[:, None])[:, :, None, None]
    col = jnp.where(upto, jnp.exp(jnp.minimum(
        ref[:, :, :, None] - G[:, :, None], SUB_SPAN)), 0.0)  # [B,N,a,C,H,K]
    full = jnp.einsum("bnatshd,bnaihd->bnshati",
                      xs * down[:, :, :, :, None], k[:, :, None] * col,
                      precision=_HIGHEST).reshape(B, N, 2, H, C, C)
    at = jnp.arange(C)
    return (jnp.where(at[None, :] < at[:, None], full[:, :, 0], 0.0),
            jnp.where(at[None, :] <= at[:, None], full[:, :, 1], 0.0))


def kda_scan(q, k, v, g, beta, state, real=None, chunk: int = 64,
             sub: int = 16):
    """A sequence q, k [B, T, H, K] float32 (normed), v [B, T, H, V], g
    [B, T, H, K] float32 (<= 0), beta [B, T, H] float32, from `state`
    [B, H, K, V] float32 -> (o [B, T, H, V] float32, the state after the
    sequence's real rows). `real` [B, T] bool: the rows a request owns
    (absent: all). `chunk` rows a chunk, in diagonal blocks of `sub` (a
    power of two of them)."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    C = chunk
    if C % sub or (C // sub) & (C // sub - 1):
        raise ValueError(f"a chunk of {C} rows: a power of two of blocks "
                         f"of {sub}")
    with jax.named_scope("kda_scan"):
        v = v.astype(jnp.float32)
        if real is not None:
            g = g * real[:, :, None, None]
            beta = beta * real[:, :, None]
        pad = -T % C
        if pad:                     # rows of g 0 and beta 0: no request's
            q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (q, k, v, g))
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        N = (T + pad) // C
        chunks = lambda a: a.reshape((B, N, C) + a.shape[2:])    # noqa: E731
        q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
        G = jnp.cumsum(g, axis=2)                                # [B,N,C,H,K]
        eG = jnp.exp(G)
        bh = beta.transpose(0, 1, 3, 2)[..., None]               # [B,N,H,C,1]
        heads = lambda a: a.transpose(0, 1, 3, 2, 4)             # noqa: E731
        Pk, Pq = _pair_weights(q, k, G, sub)
        inv = _unit_lower_inverse(bh * Pk, sub)
        U = jnp.einsum("bnhti,bnhiv->bnhtv", inv, bh * heads(v),
                       precision=_HIGHEST)
        W = jnp.einsum("bnhti,bnhid->bnhtd", inv, bh * heads(k * eG),
                       precision=_HIGHEST)
        last = G[:, :, -1]                                       # [B,N,H,K]
        left = heads(k * jnp.exp(last[:, :, None] - G))          # [B,N,H,C,K]
        qd = heads(q * eG)

        def body(S, xs):
            U, W, Pq, qd, left, last = xs
            u = U - jnp.einsum("bhtd,bhdv->bhtv", W, S, precision=_HIGHEST)
            o = jnp.einsum("bhtd,bhdv->bhtv", qd, S, precision=_HIGHEST) \
                + jnp.einsum("bhti,bhiv->bhtv", Pq, u, precision=_HIGHEST)
            S = jnp.exp(last)[..., None] * S + jnp.einsum(
                "bhtd,bhtv->bhdv", left, u, precision=_HIGHEST)
            return S, o

        state, out = jax.lax.scan(
            body, state, tuple(jnp.moveaxis(a, 1, 0)
                               for a in (U, W, Pq, qd, left, last)))
        # [N, B, H, C, V] -> [B, T, H, V]
        out = out.transpose(1, 0, 3, 2, 4).reshape(B, T + pad, H, V)
        return out[:, :T], state


class KimiDeltaAttention(nn.Module):
    """A "kda" layer's mixer (the module's docstring): `n_heads` heads of
    `kda_head_dim` for q, k and v alike. q, k, v = silu(conv(W m)), a
    depthwise causal convolution of `kda_conv` taps over each projection
    (one call over the three side by side; no bias); q and k normed to unit
    length a head, q times K^-1/2; g = `kda_gate`(W_a m) with A a head and a
    bias a channel; beta = sigmoid(W_b m), one a head; the recurrence; an
    RMSNorm over a head's output times a sigmoid gate ONE a head (W_g m);
    the output projection. Its caches are two states with no position: "s"
    [B, H, K, V] float32, and "c" [B, taps - 1, 3 H K] float32, the last
    taps - 1 real rows of the convolution's input. A call takes both in and
    hands both back WHOLE. `real` [B, L] bool: the rows a request owns."""
    cfg: Any

    @nn.compact
    def __call__(self, x, cache=None, slots=None, real=None):
        cfg = self.cfg
        B, L, E = x.shape
        H, D, taps = cfg.n_heads, cfg.kda_head_dim, cfg.kda_conv
        dense = lambda feats, axes, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=_p(nn.initializers.lecun_normal(), *axes))
        wide = ("embed", "heads", "head_dim")
        qkv = jnp.concatenate(
            [dense((H, D), wide, n)(x).reshape(B, L, H * D)
             for n in ("q", "k", "v")], axis=-1)
        conv_w = self.param("conv_w", _p(nn.initializers.lecun_normal(),
                                         None, None), (taps, 3 * H * D),
                            cfg.param_dtype)
        A_log = self.param("A_log", _p(nn.initializers.zeros, None), (H,),
                           cfg.param_dtype)
        g_bias = self.param("g_bias", _p(nn.initializers.zeros, None, None),
                            (H, D), cfg.param_dtype)
        g = kda_gate(dense((H, D), wide, "g")(x), A_log, g_bias,
                     cfg.kda_gate_floor)
        beta = jax.nn.sigmoid(
            dense(H, ("embed", "heads"), "beta")(x).astype(jnp.float32))
        gate = jax.nn.sigmoid(
            dense(H, ("embed", "heads"), "gate")(x).astype(jnp.float32))
        o_scale = self.param("o_norm", _p(nn.initializers.ones, None), (D,),
                             jnp.float32)

        def inputs(qkv, tail, real):
            conv = conv_row if qkv.shape[1] == 1 else ssm.causal_conv
            y, tail = conv(qkv, tail, conv_w, None, real, scope="kda_conv")
            with jax.named_scope("kda_conv"):
                y = nn.silu(y).reshape(y.shape[:2] + (3, H, D))
                return (unit(y[:, :, 0], D ** -0.5), unit(y[:, :, 1]),
                        y[:, :, 2], tail)

        def scan(qkv, g, beta, state, tail, real):
            q, k, v, tail = inputs(qkv, tail, real)
            o, state = kda_scan(q, k, v, g, beta, state, real,
                                cfg.kda_chunk, cfg.kda_sub)
            return o, state, tail

        def step(qkv, g, beta, state, tail, real):
            q, k, v, tail = inputs(qkv, tail, real)
            o, state = kda_step(q, k, v, g, beta, state,
                                None if real is None else real[:, 0])
            return o, state, tail

        new = None
        if cache is None:
            o, _, _ = scan(qkv, g, beta, jnp.zeros((B, H, D, D), jnp.float32),
                           jnp.zeros((B, taps - 1, 3 * H * D), jnp.float32),
                           real)
        else:
            (state, tail), _ = cache
            if slots is not None:
                (states, tails), lens, _ = slots
                n = len(lens)
                (qkv, qkv_r), (g, g_r), (beta, beta_r) = (
                    _split_rows(a, n) for a in (qkv, g, beta))
                tile_real, rows_real = (None, None) if real is None else (
                    real[:, :L - n], real[0, L - n:, None])
                o, state, tail = scan(qkv, g, beta, state, tail, tile_real)
                # always computed (no `cond` on `on`): a row no request
                # owns leaves its states as they were
                o_r, states, tails = step(qkv_r, g_r, beta_r, states, tails,
                                          rows_real)
                o = _join_rows(o, o_r)
                new = ((state, tail), (states, tails))
            else:
                o, state, tail = (scan if L > 1 else step)(
                    qkv, g, beta, state, tail, real)
                new = (state, tail)
        o = gated_norm(o, o_scale, gate, cfg.norm_eps).astype(cfg.dtype)
        out = nn.DenseGeneral(
            E, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o",
            kernel_init=_p(nn.initializers.lecun_normal(),
                           "heads", "head_dim", "embed"))(o)
        return out if cache is None else (out, new)
