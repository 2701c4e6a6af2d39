"""The plain reference of the `phi4flash` family: Phi-4-mini-flash-reasoning's
forward pass ("SambaY", arXiv:2507.06607) in straightforward jax.numpy.

float32 throughout, `default_matmul_precision("highest")`, no cache, no
tiles, no kernel, no flax, one sequence at a time, EVERY row through EVERY
layer. With u a block's input:

    h   = u + mixer(LayerNorm(u));   out = h + W_2(silu(g) * y),
                                     [g | y] = W_1 LayerNorm(h)
    logits = E LayerNorm(h_last)     (tied; no positional encoding anywhere)

and the mixer by the layer's number l of n (`kinds`: the published rule):

    l even, l <= n/2 ("s6", Mamba-1):
        [x | z] = W_in m;  x <- silu(conv_4(x) + b), causal, depthwise
        [d | B | C] = W_x x;  dt = softplus(W_dt d + b_dt)      [inner]
        S_t = exp(dt_t * A) . S_{t-1} + (dt_t x_t) B_t^T,  A = -exp(A_log)
        M_t = S_t C_t + D x_t;     mixer = W_out (M_t * silu(z_t))
        (the recurrence row by row, a `lax.scan` over positions, the state
        [inner, N]; layer n/2's M is the memory of the layers behind it)
    l odd ("win" below n/2, "att" at n/2 + 1, "xat" beyond):
        q = W_q m + b (and k, v likewise, but in "xat", which takes layer
        n/2 + 1's k and v); heads in pairs, pair i reads KV pair
        i // (pairs / KV pairs); two plain masked softmaxes a pair,
        P1 = softmax(q_2i k_2j^T / sqrt(d)), P2 = softmax(q_2i+1 k_2j+1^T /
        sqrt(d)), both against V = [v_2j | v_2j+1];
        o_i = (1 - l0) RMSNorm(P1 V - lam P2 V) * scale;  W_o concat o + b
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + l0,
        l0 = 0.8 - 0.6 exp(-0.3 l);  "win": the 512 newest positions
    l even, l > n/2 ("gmu"):  mixer = W_2 (silu(W_1 m) * M_t)

It reads the program's parameter tree (`embed`, `layer_<i>/{attn_norm, attn,
mlp_norm, mlp}`, `final_norm`). It runs in the replica beside 10 GB held, so
it upcasts ONE matrix at a time (`dot`), attends a block of query rows at a
time, takes the MLP a block of rows at a time and unembeds only the scored
positions, a block of the table's rows at a time.

Departures from the published description (`modeling_phi4flash.py`), all
inert here: W_in, W_qkv and the MLP's W_1 are kept as two or three matrices
(in_x / in_z; q / k / v; gate / up), the same products; the convolution's
taps are stored [4, inner] (tap 3 on the row itself); A_log is stored
[N, inner] (the program keeps the state's columns first); no dropout; the
published `time_step` clamps are the Mamba default (none on dt after the
softplus).
"""

from __future__ import annotations

import functools
import math

QUERY_BLOCK = 128
ROW_BLOCK = 1024             # rows of a sequence the MLP takes at a time
VOCAB_BLOCK = 12504          # 200,064 = 16 x 12,504


def kinds(n_layers: int, mb_per_layer: int = 2):
    """The published arrangement, written out: the kind of each layer, as
    the program's `mixer_kinds` names them (the family hands the program
    this list; the program has no rule of its own)."""
    out = []
    for l in range(n_layers):
        if l % mb_per_layer == 0:
            out.append("s6" if l <= n_layers // 2 else "gmu")
        elif l < n_layers // 2:
            out.append("win")
        else:
            out.append("att" if l == n_layers // 2 + 1 else "xat")
    return out


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        import jax
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def _block(n: int, most: int) -> int:
    b = most
    while n % b:
        b //= 2
    return b


def diff_attention(q, k, v, lam, lam0, scale, eps, window):
    """q [L, H, d], k and v [L, Hkv, d] -> [L, H / 2, 2 d]: two plain
    masked softmaxes a pair of heads, a block of query rows at a time."""
    import jax
    import jax.numpy as jnp
    L, H, d = q.shape
    Hkv = k.shape[1]
    pairs, kv_pairs = H // 2, Hkv // 2
    # pair i's two query heads, and the two KV heads of its KV pair
    q1, q2 = q[:, 0::2], q[:, 1::2]                     # [L, pairs, d]
    of = jnp.arange(pairs) // (pairs // kv_pairs)
    k1, k2 = k[:, 0::2][:, of], k[:, 1::2][:, of]       # [L, pairs, d]
    vv = v.reshape(L, kv_pairs, 2 * d)[:, of]           # [L, pairs, 2 d]
    qb = _block(L, QUERY_BLOCK)

    def some(i):
        rows = i * qb + jnp.arange(qb)
        seen = jnp.arange(L)[None, :] <= rows[:, None]
        if window:
            seen &= jnp.arange(L)[None, :] > rows[:, None] - window
        out = []
        for qs, ks in ((q1, k1), (q2, k2)):
            s = jnp.einsum("qpd,mpd->pqm",
                           jax.lax.dynamic_slice_in_dim(qs, i * qb, qb, 0),
                           ks) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            out.append(jnp.einsum("pqm,mpe->qpe", p, vv))
        o = out[0] - lam * out[1]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        return o * scale * (1.0 - lam0)

    return jax.lax.map(some, jnp.arange(L // qb)).reshape(
        L, pairs, 2 * d)


def s6_with_state(x, dt, A, B, C, D, n):
    """(M [L, I] of the recurrence, row by row from S_0 = 0; S [k, I, N]
    after the first n[i] rows each). x and dt [L, I], A [I, N], B and C
    [L, N], D [I]."""
    import jax
    import jax.numpy as jnp
    L, I = x.shape
    N = B.shape[1]

    def step(carry, xs):
        S, kept = carry
        xt, dtt, bt, ct, t = xs
        S = jnp.exp(dtt[:, None] * A) * S \
            + (dtt * xt)[:, None] * bt[None, :]
        kept = jnp.where((t == n - 1)[:, None, None], S, kept)
        return (S, kept), S @ ct + D * xt

    (_, kept), y = jax.lax.scan(
        step, (jnp.zeros((I, N), jnp.float32),
               jnp.zeros((len(n), I, N), jnp.float32)),
        (x, dt, B, C, jnp.arange(L)))
    return y, kept


_SHAPE_KEYS = ("layer_norm_eps", "num_attention_heads",
               "num_key_value_heads", "sliding_window", "hidden_size")


def _fns(m):
    a = m["assumed_sizes"]
    return _fns_of(tuple(m[k] for k in _SHAPE_KEYS)
                   + (a["d_state"], a["d_conv"], a["dt_rank"]))


@functools.lru_cache(maxsize=None)
def _fns_of(key):
    """Jitted pieces, one compile each per shape."""
    import jax
    import jax.numpy as jnp
    eps, H, Hkv, window, hidden, N, K, R = key
    d = hidden // H

    @jax.jit
    def norm(x, p):
        x = x - jnp.mean(x, -1, keepdims=True)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"].astype(jnp.float32) \
            + p["bias"].astype(jnp.float32)

    @jax.jit
    @_highest
    def dot(x, w):
        """x [L, a..] @ w["kernel"] [a.., ...] (upcast here, alone), plus
        its bias where it has one -> [L, prod(...)]"""
        x = x.reshape(x.shape[0], -1)
        y = x @ w["kernel"].astype(jnp.float32).reshape(x.shape[1], -1)
        if "bias" in w:
            y = y + w["bias"].astype(jnp.float32).reshape(-1)
        return y

    @functools.partial(jax.jit, static_argnums=(5, 6))
    @_highest
    def heads(q, k, v, p, lam0, windowed, L):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0
        return diff_attention(
            q.reshape(L, H, d), k.reshape(L, Hkv, d), v.reshape(L, Hkv, d),
            lam, lam0, p["subln"], eps, window if windowed else 0)

    @jax.jit
    @_highest
    def core(xs, p, n):
        """The "s6" mixer between its in- and out-projections: xs [L, I]
        -> (M [L, I]; for each n[i] the state [I, N] after the first n[i]
        rows and the convolution's input's last K - 1 rows before row
        n[i])."""
        w = {a: p[a].astype(jnp.float32)
             for a in ("conv_w", "conv_b", "A_log", "D")}
        L = xs.shape[0]
        padded = jnp.concatenate([jnp.zeros((K - 1, xs.shape[1])), xs])
        tail = jax.vmap(lambda i: jax.lax.dynamic_slice_in_dim(
            padded, i, K - 1, 0))(n)
        x = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + L]
                            for j in range(K)) + w["conv_b"])
        dbc = x @ p["x_proj"]["kernel"].astype(jnp.float32)
        dt = jax.nn.softplus(
            dbc[:, :R] @ p["dt_proj"]["kernel"].astype(jnp.float32)
            + p["dt_proj"]["bias"].astype(jnp.float32))
        y, state = s6_with_state(
            x, dt, -jnp.exp(w["A_log"]).T, dbc[:, R:R + N], dbc[:, R + N:],
            w["D"], n)
        return y, state, tail

    @jax.jit
    def gate(a, b):
        return jax.nn.silu(a) * b

    return norm, dot, heads, core, gate


def hidden_states(params, m: dict, tokens, states_after=None):
    """Final-norm hidden states [L, d] of one sequence `tokens` [L]; with
    `states_after` = (n, ..) also each "s6" layer's states [k, I, N] after
    the first n tokens and its convolution's tails [k, K - 1, I] there, in
    the layers' order."""
    import jax.numpy as jnp
    norm, dot, heads, core, gate = _fns(m)
    L = len(tokens)
    n = (L,) if states_after is None else tuple(states_after)
    rows = min(L, ROW_BLOCK)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    states, tails = [], []
    mem = kv = None
    for l, kind in enumerate(kinds(m["num_hidden_layers"],
                                   m["mb_per_layer"])):
        p = params[f"layer_{l}"]
        a = p["attn"]
        u = norm(x, p["attn_norm"])
        if kind == "s6":
            mem, state, tail = core(
                dot(u, a["in_x"]),
                {k: a[k] for k in ("conv_w", "conv_b", "A_log", "D",
                                   "x_proj", "dt_proj")},
                jnp.asarray(n, jnp.int32))
            states.append(state)
            tails.append(tail)
            mix = dot(gate(dot(u, a["in_z"]), mem), a["out"])
        elif kind == "gmu":
            mix = dot(gate(dot(u, a["in"]), mem), a["out"])
        else:
            if kind != "xat":
                kv = dot(u, a["k"]), dot(u, a["v"])
            lam0 = 0.8 - 0.6 * math.exp(-0.3 * l)
            mix = dot(heads(
                dot(u, a["q"]), *kv,
                {k: a[k] for k in ("lambda_q1", "lambda_k1", "lambda_q2",
                                   "lambda_k2", "subln")},
                lam0, kind == "win", L), a["o"])
        x = x + mix
        w = p["mlp"]
        u = norm(x, p["mlp_norm"])
        x = x + jnp.concatenate([
            dot(gate(dot(u[at:at + rows], w["gate"]),
                     dot(u[at:at + rows], w["up"])), w["down"])
            for at in range(0, L, rows)])
    h = norm(x, params["final_norm"])
    return h if states_after is None else (h, states, tails)


def _vocab_blocks(params, m: dict, h):
    """Blocks of the logits [rows, block] of final-norm rows h, over the
    vocabulary in order (the table is the head: tied)."""
    _, dot, *_ = _fns(m)
    V = m["vocab_size"]
    vb = next(b for b in (VOCAB_BLOCK, 4096, 1024, 128, 1) if V % b == 0)
    for at in range(0, V, vb):
        yield dot(h, {"kernel": params["embed"][at:at + vb].T})


def logits(params, m: dict, tokens, rows=None):
    """[L, vocab] float32 next-token logits of one sequence, or of its
    positions rows = (first, end) alone."""
    import jax.numpy as jnp
    h = hidden_states(params, m, tokens)
    if rows is not None:
        h = h[rows[0]:rows[1]]
    return jnp.concatenate(list(_vocab_blocks(params, m, h)), axis=-1)


def batch_loss(params, m: dict, batch):
    """Mean next-token cross-entropy of a batch [B, L + 1] (no cell trains
    this model; the harness asks every family for it)."""
    import jax
    import jax.numpy as jnp
    total = 0.0
    for row in batch:
        row = jnp.asarray(row)
        lg = logits(params, m, row[:-1])
        gold = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
        total += float(jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold))
    return total / len(batch)


def teacher_forced_gaps(params, m: dict, prompt, generated, pad_to=None,
                        with_spread=False, with_rows=False, also=()):
    """For each generated token: the largest reference logit at its
    position minus the reference logit of the token the system chose. One
    pass over prompt + generated; `pad_to` pads the sequence at its end
    (every layer is causal). `with_rows`: -> {"gaps", "spread", "rows" (the
    reference's logits [len(generated), vocab] at the scored positions),
    "also" (its logits at the positions `also`), "states" and "tails" (each
    "s6" layer's state [2, I, N] and tail [2, K - 1, I] after the prompt
    [0] and after the last scored position [1])}."""
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
