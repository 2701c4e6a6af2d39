"""The plain reference of the `falcon_h1` family: Falcon-H1's forward pass
and next-token loss in straightforward jax.numpy.

float32 throughout, `default_matmul_precision("highest")`, no cache, no
tiles, no chunks, no kernel, no batching (one sequence at a time), no flax.
Every block runs attention heads and a Mamba-2 state-space mixer on ONE
normed input and sums them (`config.json`'s keys in capitals where a
multiplier):

    e      = embed[ids] * EMBEDDING
    u      = RMSNorm(h)
    q,k,v  = W_q a, W_k a, W_v a          a = u * ATTENTION_IN
    k      = k * KEY;  q, k = rope(q, k);  softmax(q k^T / sqrt(128)), causal
    att    = W_o(heads) * ATTENTION_OUT    (20 heads over 4 KV heads: head h
                                            reads KV head h // 5)
    z | xBC | dt = W_in (u * SSM_IN), the segments z, x, B, C, dt each times
                                            its own of SSM_MULTIPLIERS
    xBC    = silu(sum over 4 taps of w_j * xBC[t - 3 + j] + bias)
    dt_t   = softplus(dt_t + dt_bias_h);   A_h = -exp(A_log_h)
    S_t    = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T     (a head, 128 x 256;
                                            B, C of the head's group h // 16)
    y_t    = S_t C_t + D_h x_t
    y      = RMSNorm a group of 2048 of (y * silu(z)), one learned scale
    ssm    = W_out y * SSM_OUT
    h      = h + att + ssm
    h      = h + W_down(silu(W_gate m * MLP[0]) * W_up m) * MLP[1]
                                            m = RMSNorm(h)
    logits = W_head RMSNorm(h) * LM_HEAD

The recurrence is a `lax.scan` over positions: the recurrence itself.

It reads the program's parameter tree (`embed`, `layer_<i>/{attn_norm,
attn, ssm, mlp_norm, mlp}`, `final_norm`, `unembed`). It runs in the replica
beside 13-14 GB held, so it upcasts ONE matrix at a time (`_dot`), attends a
block of query rows at a time, and unembeds only the scored positions, a
block of vocabulary rows at a time. Its peak at 4,096 positions is the MLP's:
one matrix in float32 (0.44 GB) and XLA's three bf16 pieces of it for a
product at HIGHEST (0.66 GB) beside three [1024, 21504] float32 blocks
(0.26 GB): about 1.4 GB, and what the host has queued ahead of the device
beside it; `hbm_peak_gb` reads the whole (PERF.md section 4).

Departures from the published description (`transformers`
`modeling_falcon_h1.py`), all inert here: no attention or projection bias
(the config's are false), no `time_step_limit` clamp (0..inf), no
`rope_scaling` (null), the in-projection kept as three matrices (z, xBC,
dt) and not one of 9,248 columns, the convolution's taps stored [4, 5120]
(tap 3 on the row itself).
"""

from __future__ import annotations

import functools

QUERY_BLOCK = 256
ROW_BLOCK = 1024             # rows of a sequence the MLP takes at a time
VOCAB_BLOCK = 16320          # 261,120 = 16 x 16,320


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        import jax
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x [L, H, D], positions 0..L-1, rotate-half."""
    import jax.numpy as jnp
    L, _, D = x.shape
    inv = float(theta) ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(n: int, most: int) -> int:
    """The largest power-of-two block up to `most` that divides n."""
    b = most
    while n % b:
        b //= 2
    return b


# ---------------------------------------------------------------- the mixers
def attention(q, k, v):
    """Full causal attention: q [L, H, D], k and v [L, Hkv, D] -> [L, H, D],
    a block of query rows at a time."""
    import jax
    import jax.numpy as jnp
    L, H, D = q.shape
    Hkv = k.shape[1]
    qb = _block(L, QUERY_BLOCK)

    def some(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0).reshape(
            qb, Hkv, H // Hkv, D)
        s = jnp.einsum("qhgd,mhd->hgqm", qs, k) / jnp.sqrt(float(D))
        seen = jnp.arange(L)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqm,mhd->qhgd", a, v).reshape(qb, H, D)

    return jax.lax.map(some, jnp.arange(L // qb)).reshape(L, H, D)


def conv_taps(x, w, b):
    """The depthwise causal convolution as a sum over its taps: x [L, C],
    w [K, C] (the last tap on the row itself), b [C]; zeros before the
    sequence."""
    import jax.numpy as jnp
    L, K = x.shape[0], w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * xp[j:j + L] for j in range(K)) + b


def ssd_with_state(x, dt, A, B, C, D, n=None):
    """(y [L, H, P] of the recurrence, token by token from S_0 = 0, and
    S [H, P, N] after the last token; with `n` [k] (traced), S [k, H, P, N]
    after the first n[i] tokens each). x [L, H, P], dt [L, H], A and D
    [H], B and C [L, G, N]."""
    import jax
    import jax.numpy as jnp
    L, H, P = x.shape
    G, N = B.shape[1:]
    at = jnp.asarray([L]) if n is None else n

    def step(carry, xs):
        S, kept = carry
        xt, dtt, bt, ct, t = xs
        bh, ch = (jnp.repeat(a, H // G, axis=0) for a in (bt, ct))  # [H, N]
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :]
        kept = jnp.where((t == at - 1)[:, None, None, None], S, kept)
        return (S, kept), jnp.einsum("hpn,hn->hp", S, ch) + D[:, None] * xt

    zero = jnp.zeros((H, P, N), jnp.float32)
    (_, kept), y = jax.lax.scan(
        step, (zero, jnp.zeros((len(at), H, P, N), jnp.float32)),
        (x, dt, B, C, jnp.arange(L)))
    return y, (kept[0] if n is None else kept)


# ------------------------------------------------------------------ layers
_SHAPE_KEYS = ("rms_norm_eps", "rope_theta", "num_attention_heads",
               "num_key_value_heads", "head_dim", "mamba_n_heads",
               "mamba_d_head", "mamba_d_state", "mamba_n_groups",
               "mamba_d_conv", "attention_in_multiplier",
               "attention_out_multiplier", "key_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier")


def _fns(m):
    return _fns_of(tuple(m[k] for k in _SHAPE_KEYS)
                   + (tuple(m["ssm_multipliers"]),
                      tuple(m["mlp_multipliers"])))


@functools.lru_cache(maxsize=None)
def _fns_of(key):
    """Jitted pieces, one compile each per shape: a norm, ONE matrix
    upcast and multiplied, the heads' attention, the state-space core
    between its projections, the MLP's gate."""
    import jax
    import jax.numpy as jnp
    (eps, theta, H, Hkv, hd, MH, P, N, G, K, a_in, a_out, k_mult, s_in,
     s_out, s_mults, mlp_mults) = key
    inner = MH * P

    @jax.jit
    def norm(x, scale):
        return _rms(x, scale.astype(jnp.float32), eps)

    @jax.jit
    @_highest
    def dot(x, w):
        """x [L, a] @ w [a, ...] (upcast here, alone) -> [L, prod(...)]"""
        return x @ w.astype(jnp.float32).reshape(x.shape[1], -1)

    @jax.jit
    @_highest
    def heads(q, k, v):
        L = q.shape[0]
        q = _rope(q.reshape(L, H, hd), theta)
        k = _rope(k.reshape(L, Hkv, hd) * k_mult, theta)
        return attention(q, k, v.reshape(L, Hkv, hd)).reshape(L, H * hd)

    @jax.jit
    @_highest
    def core(z, xbc, dt, p, n):
        """The mixer between its projections -> (the gated, normed y
        [L, inner]; for each n[i] the state after the first n[i] tokens
        and the convolution's input's last K - 1 rows before position
        n[i])."""
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        L = z.shape[0]
        mz, mx, mb, mc, mdt = s_mults
        z = z * mz
        xbc = xbc * jnp.asarray([mx] * inner + [mb] * (G * N)
                                + [mc] * (G * N), jnp.float32)
        padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
        tail = jax.vmap(lambda i: jax.lax.dynamic_slice_in_dim(
            padded, i, K - 1, 0))(n)
        act = jax.nn.silu(conv_taps(xbc, p["conv_w"], p["conv_b"]))
        x = act[:, :inner].reshape(L, MH, P)
        B = act[:, inner:inner + G * N].reshape(L, G, N)
        C = act[:, inner + G * N:].reshape(L, G, N)
        dt = jax.nn.softplus(dt * mdt + p["dt_bias"])
        y, state = ssd_with_state(x, dt, -jnp.exp(p["A_log"]), B, C,
                                  p["D"], n)
        y = (y.reshape(L, inner) * jax.nn.silu(z)).reshape(
            L, G, inner // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return y.reshape(L, inner) * p["norm_scale"], state, tail

    @jax.jit
    def gated(g, up):
        return jax.nn.silu(g * mlp_mults[0]) * up

    scal = dict(a_in=a_in, a_out=a_out, s_in=s_in, s_out=s_out,
                down=mlp_mults[1])
    return norm, dot, heads, core, gated, scal


def hidden_states(params, m: dict, tokens, states_after=None):
    """Final-norm hidden states [L, d] of one sequence `tokens` [L]; with
    `states_after` = (n, ..) also each layer's states [k, H, P, N] after
    the first n tokens and its convolution's tails [k, K - 1, channels]
    there, in the layers' order."""
    import jax.numpy as jnp
    norm, dot, heads, core, gated, c = _fns(m)
    L = len(tokens)
    n = (L,) if states_after is None else tuple(states_after)
    rows = min(L, ROW_BLOCK)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32) \
        * m["embedding_multiplier"]
    states, tails = [], []
    small = ("conv_w", "conv_b", "dt_bias", "A_log", "D", "norm_scale")
    for i in range(m["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        a, s = p["attn"], p["ssm"]
        u = norm(x, p["attn_norm"]["scale"])
        ua = u * c["a_in"]
        att = dot(heads(*(dot(ua, a[w]["kernel"]) for w in "qkv")),
                  a["o"]["kernel"]) * c["a_out"]
        us = u * c["s_in"]
        y, state, tail = core(
            *(dot(us, s[w]["kernel"]) for w in ("in_z", "in_xbc", "in_dt")),
            {k: s[k] for k in small}, jnp.asarray(n, jnp.int32))
        states.append(state)
        tails.append(tail)
        x = x + att + dot(y, s["out"]["kernel"]) * c["s_out"]
        w = p["mlp"]
        u = norm(x, p["mlp_norm"]["scale"])
        x = x + jnp.concatenate([
            dot(gated(dot(u[at:at + rows], w["gate"]["kernel"]),
                      dot(u[at:at + rows], w["up"]["kernel"])),
                w["down"]["kernel"])
            for at in range(0, L, rows)]) * c["down"]
    h = norm(x, params["final_norm"]["scale"])
    return h if states_after is None else (h, states, tails)


def _vocab_blocks(params, m: dict, h):
    """Blocks of the logits [rows, block] of final-norm rows h, over the
    vocabulary in order."""
    _, dot, *_ = _fns(m)
    V = m["vocab_size"]
    vb = next(b for b in (VOCAB_BLOCK, 4096, 1024, 257, 1) if V % b == 0)
    for at in range(0, V, vb):
        yield dot(h, params["unembed"][:, at:at + vb]) \
            * m["lm_head_multiplier"]


def logits(params, m: dict, tokens, rows=None):
    """[L, vocab] float32 next-token logits of one sequence, or of its
    positions rows = (first, end) alone."""
    import jax.numpy as jnp
    h = hidden_states(params, m, tokens)
    if rows is not None:
        h = h[rows[0]:rows[1]]
    return jnp.concatenate(list(_vocab_blocks(params, m, h)), axis=-1)


def sequence_loss(params, m: dict, tokens):
    """Mean next-token cross-entropy of one sequence [L + 1]."""
    import jax
    import jax.numpy as jnp
    tokens = jnp.asarray(tokens)
    lg = logits(params, m, tokens[:-1])
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


def batch_loss(params, m: dict, batch):
    return sum(float(sequence_loss(params, m, row)) for row in batch) \
        / len(batch)


def teacher_forced_gaps(params, m: dict, prompt, generated, pad_to=None,
                        with_spread=False, with_rows=False, also=()):
    """For each generated token: the largest reference logit at its
    position minus the reference logit of the token the system chose. One
    pass over prompt + generated; `pad_to` pads the sequence at its end
    (every layer is causal: later positions change no earlier one).
    `with_rows`: -> {"gaps", "spread" (the logits'), "rows" (the
    reference's logits [len(generated), vocab] at the scored positions),
    "also" (its logits at the positions `also`), "states" and "tails"
    (each layer's state and convolution's tail after the prompt [0] and
    after the last scored position [1])}."""
    import jax.numpy as jnp
    import numpy as np
    seq = (list(prompt) + list(generated))[:-1]
    n = len(seq)
    seq = seq + [0] * max(0, (pad_to or 0) - n)
    h, states, tails = hidden_states(params, m, seq,
                                     states_after=(len(prompt), n))
    rows = jnp.concatenate(list(_vocab_blocks(
        params, m, h[len(prompt) - 1:n])), axis=-1)
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(generated)[:, None], axis=-1)[:, 0]
    gaps = np.asarray(rows.max(-1) - chosen, np.float64).tolist()
    if with_rows or with_spread:
        spread = float(jnp.std(rows, axis=-1).mean())
        if not with_rows:
            return gaps, spread
        return {"gaps": gaps, "spread": spread, "rows": rows,
                "also": jnp.concatenate(list(_vocab_blocks(
                    params, m, h[jnp.asarray(also)])), axis=-1)
                if len(also) else None,
                "states": states, "tails": tails}
    return gaps
