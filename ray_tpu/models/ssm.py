"""A state-space mixer with an input-dependent decay (Mamba-2's SSD) and
the short causal convolution before it: two states with no position in
place of a cache of keys and values.

For each head h, with x_t [P], a step dt_t > 0, A_h < 0, D_h, and B_t, C_t
[N] shared by the heads of a GROUP, a state S [P, N] in float32:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

It is models/linear_attention.py's recurrence with q = C, k = B, v = dt x
and the log-decay `dt_t A_h` of each token in place of a head's constant;
it is a function of its own because B and C belong to a group of heads:
the causal products C_t . B_u are formed once a GROUP (2 here, for 32
heads) and only the decays a head, which a scan that takes q and k a head
would compute sixteen times over.

Two forms of the same mathematics, chosen by the caller from what it holds
(models/transformer.py `Mamba2Mixer`):

- `ssd_scan`: a sequence (a prefill tile) that starts from a state and
  hands one on, chunk by chunk: inside a chunk the causal products
  weighted by the decays between the two rows, between chunks through the
  state.
- `ssd_step`: one row a slot, the recurrence itself, elementwise in
  float32.

`causal_conv` is the depthwise convolution of width K over the channels of
[x, B, C] before the recurrence. Its state is its input's TAIL: the last
K - 1 rows a request owns, carried from tile to tile and from decode row to
decode row as the other state is.

A second recurrence, Mamba-1's ("S6": `s6_scan`, `s6_step`, below), is a
layer's whole mixer where the first is a branch: its decay differs by
channel AND by state column, so it has no matmul form.

A row no request owns (`real` False: a tile's padded tail, a dead slot's
row behind a tile) neither decays the state nor adds to it nor enters the
tail. Every decay is exp of a DIFFERENCE of cumulated exponents, never
positive, so nothing overflows though `dt A` is unbounded below; the state
and every product that touches it are float32 at precision HIGHEST.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 128          # rows a chunk of `ssd_scan` holds
S6_CHUNK = 16        # rows `s6_scan` unrolls in one turn of its loop
_HIGHEST = jax.lax.Precision.HIGHEST


def causal_conv(x, tail, w, b, real=None, scope: str = "ssm_conv"):
    """x [B, T, C] after `tail` [B, K - 1, C] (the K - 1 rows before it;
    zeros for a fresh sequence), taps w [K, C] (the last on the row
    itself) and bias b [C] (None: no bias) -> (y [B, T, C] float32, the new
    tail in `tail`'s type: the last K - 1 of the rows before x and x's REAL
    rows). `real` [B, T] bool, a prefix of each row (the engine pads a
    tile at its end); absent: all. `scope`: the name the trace files it
    under."""
    B, T, _ = x.shape
    K = w.shape[0]
    with jax.named_scope(scope):
        xin = jnp.concatenate([tail.astype(jnp.float32),
                               x.astype(jnp.float32)], axis=1)
        w32 = w.astype(jnp.float32)
        y = sum(w32[j] * xin[:, j:j + T] for j in range(K))
        if b is not None:
            y = y + b.astype(jnp.float32)
        n_real = jnp.full((B,), T, jnp.int32) if real is None \
            else jnp.sum(real, axis=1, dtype=jnp.int32)
        new = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
            rows, n, K - 1, axis=0))(xin, n_real)
        return y, new.astype(tail.dtype)


def _grouped(a, G: int):
    """[B, T, H, ..] -> [B, T, G, H / G, ..]: the heads by their group."""
    return a.reshape(a.shape[:2] + (G, a.shape[2] // G) + a.shape[3:])


def ssd_step(x, dt, A, Bm, Cm, D, state, real=None):
    """One row a slot: x [B, 1, H, P], dt [B, 1, H] float32 (> 0), A and D
    [H], Bm and Cm [B, 1, G, N], state [B, H, P, N] float32 -> (y
    [B, 1, H, P] in x's type, the new state). The row sees itself: the
    state is advanced first. `real` [B] bool: a row that is not leaves its
    state as it was."""
    B, _, H, P = x.shape
    G = Bm.shape[2]
    with jax.named_scope("ssd_step"):
        x32, dt32 = x[:, 0].astype(jnp.float32), dt[:, 0]
        heads = lambda a: jnp.repeat(                       # noqa: E731
            a[:, 0].astype(jnp.float32), H // G, axis=1)    # [B, H, N]
        decay = jnp.exp(dt32 * A.astype(jnp.float32))
        new = decay[..., None, None] * state \
            + (dt32[..., None] * x32)[..., :, None] * heads(Bm)[..., None, :]
        y = jnp.sum(new * heads(Cm)[..., None, :], axis=-1) \
            + D.astype(jnp.float32)[:, None] * x32
        if real is not None:
            new = jnp.where(real[:, None, None, None], new, state)
        return y[:, None].astype(x.dtype), new


def ssd_scan(x, dt, A, Bm, Cm, D, state, real=None, chunk: int = CHUNK):
    """A sequence x [B, T, H, P], dt [B, T, H] float32 (> 0), A and D [H],
    Bm and Cm [B, T, G, N], from `state` [B, H, P, N] float32 -> (y
    [B, T, H, P] in x's type, the state after the sequence's real rows).
    `real` [B, T] bool: the rows a request owns (absent: all)."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    J = H // G
    C = min(chunk, T)
    pad = -T % C
    if real is None:
        real = jnp.ones((B, T), bool)
    if pad:
        x, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                     for a in (x, Bm, Cm))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        real = jnp.pad(real, ((0, 0), (0, pad)))
    n = (T + pad) // C
    # [n, B, C, ..]: the chunks are the scan's axis
    chunks = lambda a: a.reshape((B, n, C) + a.shape[2:]).swapaxes(0, 1)  # noqa: E731,E501
    A32 = A.astype(jnp.float32)
    causal = jnp.tril(jnp.ones((C, C), bool))

    def body(S, xs):
        xn, dtn, bn, cn, rn = xs
        r32 = rn.astype(jnp.float32)
        dtr = dtn * r32[..., None]                   # 0 on a row not real
        # b[t]: the exponent cumulated over the chunk's real rows to t
        b = jnp.cumsum(dtr * A32, axis=1)                         # [B,C,H]
        bh = b.transpose(0, 2, 1).reshape(B, G, J, C)
        # the decay from row u to row t, times what row u adds (dt_u, and
        # nothing where it is not real: its column is zeroed)
        L = jnp.where(causal, jnp.exp(jnp.where(
            causal, bh[..., :, None] - bh[..., None, :], 0.0)), 0.0) \
            * dtr.transpose(0, 2, 1).reshape(B, G, J, 1, C)
        cb = jnp.einsum("btgn,bugn->bgtu", cn, bn,
                        preferred_element_type=jnp.float32)
        xg = _grouped(xn, G)                                    # [B,C,G,J,P]
        y = jnp.einsum("bgjtu,bugjp->btgjp",
                       (cb[:, :, None] * L).astype(xn.dtype), xg,
                       preferred_element_type=jnp.float32)
        Sg = S.reshape(B, G, J, P, N)
        y = y + jnp.einsum("btgn,bgjpn->btgjp", cn.astype(jnp.float32), Sg,
                           precision=_HIGHEST) \
            * _grouped(jnp.exp(b), G)[..., None]
        left = _grouped(jnp.exp(b[:, -1:] - b) * dtr, G)[..., None]
        Sg = jnp.exp(bh[..., -1])[..., None, None] * Sg + jnp.einsum(
            "bugjp,bugn->bgjpn", xg.astype(jnp.float32) * left,
            bn.astype(jnp.float32), precision=_HIGHEST)
        y = y.reshape(B, C, H, P) \
            + D.astype(jnp.float32)[:, None] * xn.astype(jnp.float32)
        return Sg.reshape(B, H, P, N), y.astype(x.dtype)

    with jax.named_scope("ssd_scan"):
        state, out = jax.lax.scan(
            body, state, tuple(chunks(a) for a in (x, dt, Bm, Cm, real)))
        out = out.swapaxes(0, 1).reshape(B, T + pad, H, P)
        return out[:, :T], state


# ------------------------------------------------------------ Mamba-1 (S6)
# For each of I channels i, with x_t, a step dt_t > 0 A CHANNEL, A [N, I]
# < 0, D [I], and B_t, C_t [N] shared by all channels, a state S [N, I] in
# float32:
#
#     S_t = exp(dt_t[i] A[n, i]) S_{t-1} + dt_t[i] x_t[i] B_t[n]
#     y_t[i] = sum_n S_t[n, i] C_t[n] + D[i] x_t[i]
#
# The decay exp(dt_t[i] A[n, i]) differs in every element of the state, so
# the products of a chunk's rows are no matmul (`ssd_scan`'s decay is one
# number a head): the recurrence is elementwise, 7 operations an element of
# the state a row. The state keeps its N columns FIRST and the channels in
# the lanes ([N, I], 16 x 5120: whole vector registers; [I, N] would fill
# an eighth of each). The scan's form, by count (PERF.md section 6, PR 53):
# a `lax.scan` a ROW pays the loop's turn and its few fusions' launches a
# row, 1,024 times a layer a tile, to move 30 KB; an associative scan passes
# [rows, N, I] float32 through HBM a dozen times. `s6_scan` is between: a
# `lax.scan` over chunks of S6_CHUNK rows that carries the state, the
# chunk's rows unrolled, so that a turn's intermediates are [S6_CHUNK, N, I]
# (5 MB) and no array is [rows, N, I] a tile long.


def _s6_row(S, x, dt, A, Bm, Cm, D):
    """One row of the recurrence: S [B, N, I] float32, x and dt [B, I]
    float32 (dt 0 on a row no request owns: the state is then left as it
    was, exactly), Bm and Cm [B, N] float32 -> (the new state, y [B, I])."""
    S = jnp.exp(dt[:, None, :] * A) * S \
        + (dt * x)[:, None, :] * Bm[:, :, None]
    return S, jnp.sum(S * Cm[:, :, None], axis=1) + D * x


def s6_step(x, dt, A, Bm, Cm, D, state, real=None):
    """One row a slot: x [B, 1, I], dt [B, 1, I] float32 (> 0), A [N, I]
    float32 (< 0), Bm and Cm [B, 1, N], D [I], state [B, N, I] float32 ->
    (y [B, 1, I] in x's type, the new state). `real` [B] bool: a row that
    is not leaves its state as it was."""
    with jax.named_scope("s6_step"):
        dt = dt[:, 0] if real is None else dt[:, 0] * real[:, None]
        new, y = _s6_row(state, x[:, 0].astype(jnp.float32), dt, A,
                         Bm[:, 0].astype(jnp.float32),
                         Cm[:, 0].astype(jnp.float32),
                         D.astype(jnp.float32))
        return y[:, None].astype(x.dtype), new


def s6_scan(x, dt, A, Bm, Cm, D, state, real=None, chunk: int = S6_CHUNK):
    """A sequence x [B, T, I], dt [B, T, I] float32 (> 0), A [N, I]
    float32, Bm and Cm [B, T, N], D [I], from `state` [B, N, I] float32 ->
    (y [B, T, I] in x's type, the state after the sequence's real rows).
    `real` [B, T] bool: the rows a request owns (absent: all)."""
    B, T, I = x.shape
    C = min(chunk, T)
    pad = -T % C
    if real is not None:
        dt = dt * real[..., None]
    if pad:                             # rows of dt 0: no request's
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                         for a in (x, dt, Bm, Cm))
    n = (T + pad) // C
    # [n, C, B, ..]: the chunks are the scan's axis, a chunk's rows next
    chunks = lambda a: a.reshape((B, n, C) + a.shape[2:]).transpose(  # noqa: E731,E501
        1, 2, 0, 3)
    D32 = D.astype(jnp.float32)

    def body(S, xs):
        xn, dtn, bn, cn = xs
        xn, bn, cn = (a.astype(jnp.float32) for a in (xn, bn, cn))
        ys = []
        for t in range(C):
            S, y = _s6_row(S, xn[t], dtn[t], A, bn[t], cn[t], D32)
            ys.append(y)
        return S, jnp.stack(ys).astype(x.dtype)

    with jax.named_scope("s6_scan"):
        state, out = jax.lax.scan(
            body, state, tuple(chunks(a) for a in (x, dt, Bm, Cm)))
        out = out.transpose(2, 0, 1, 3).reshape(B, T + pad, I)
        return out[:, :T], state
