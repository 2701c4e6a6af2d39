"""The plain reference of the `minicpm_sala` family: MiniCPM-SALA's forward
pass and next-token loss in straightforward jax.numpy.

float32 throughout, `default_matmul_precision("highest")`, no cache, no
tiles, no kernel, no batching (one sequence at a time), no flax. With
`u = RMSNorm(x)` and `c = scale_depth / sqrt(published depth)`:

    x <- x + c Mixer(u);   x <- x + c SwiGLU(RMSNorm(x))

the embedding's rows times `scale_emb`, and the head sees the final
RMSNorm's output over `hidden_size / dim_model_base`. The mixer is one of
two kinds, by the configuration's `mixer_types`:

"lightning-attn" (32 heads of 128 for q, k and v alike):

    q_t, k_t = rope(norm(W_q u_t)), rope(norm(W_k u_t));   v_t = W_v u_t
    S_t = lambda_h S_{t-1} + k_t v_t^T        (a head, 128 x 128, S_0 = 0)
    o_t = q_t^T S_t / sqrt(128),   lambda_h = exp(-2^(-8 h / 32)), h = 1..32
    y_t = W_o (norm(o_t) * sigmoid(W_g u_t))

token by token (a `lax.scan` over positions: the recurrence itself).

"minicpm4" (32 heads, 2 KV heads, no rotary), with `sparse_config`'s block
64, kernel 32, stride 16, 1 initial block, window 2048, 64 blocks in all:

    q_t = norm(W_q u_t);  k_t = norm(W_k u_t);  v_t = W_v u_t
    c_j = mean(k[16 j : 16 j + 32])           for 16 j + 31 <= t
    p[h, j] = softmax_j(q_t[h] . c_j / sqrt(128))
    r_j = sum of p[h, j] over the 16 heads of a KV group
    R_b = max r_j over j = 4 b - 1 .. 4 b + 3
    B_t = block 0, the blocks that hold positions t - 2047 .. t, and the
          best of the other blocks with 64 b <= t by R_b (ties to the
          lower block) until 64 are taken; every visible block where no
          more than 64 are
    o_t[h] = sum over s <= t in B_t of
             softmax_s(q_t[h] . k_s[g(h)] / sqrt(128)) v_s[g(h)]
    y_t = W_o (o_t * sigmoid(W_g u_t))

a query at a time in its mathematics, a block of queries at a time in its
arrays (the selection is an explicit ranking of each query's block
scores), so that 8,704 positions fit beside the served weights on one chip.

It reads the program's parameter tree (`embed`, `layer_<i>/...`,
`final_norm`, `unembed`) and upcasts one layer at a time.

Assumed, where the published `config.json` is silent (the configuration's
`assumed` says the same): no activation on q, k, v of a lightning layer;
one fixed decay a head, the same in every layer (Lightning Attention-2);
the output norm's span is a head; the gates are hidden -> heads x head
size; the sparse sizes above (MiniCPM4's `sparse_config`, InfLLM-V2) and
the forms of p, r and R; RMSNorm on each head of q and k with one learned
scale over the head size.
"""

from __future__ import annotations

import functools

QUERY_BLOCK = 128
ROW_BLOCK = 256


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
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _f32(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def residual_scale(m: dict) -> float:
    """scale_depth / sqrt(the PUBLISHED depth), whatever the cut."""
    return m["scale_depth"] / m["reduced"]["num_hidden_layers"][
        "published"] ** 0.5


# --------------------------------------------------------------- lightning
def lightning_with_state(q, k, v, n=None):
    """(o [L, H, D] of the recurrence, token by token from S_0 = 0, and
    S [H, D, D] after the first `n` tokens: after the last where None)."""
    import jax
    import jax.numpy as jnp
    L, H, D = q.shape
    lam = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, H + 1) / H))
    n = L if n is None else n

    def step(carry, qkvt):
        S, kept = carry
        qt, kt, vt, t = qkvt
        S = lam[:, None, None] * S + kt[:, :, None] * vt[:, None, :]
        kept = jnp.where(t == n - 1, S, kept)
        return (S, kept), jnp.einsum("hd,hde->he", qt, S) / jnp.sqrt(float(D))

    zero = jnp.zeros((H, D, D), jnp.float32)
    (_, kept), o = jax.lax.scan(step, (zero, zero), (q, k, v, jnp.arange(L)))
    return o, kept


def lightning(q, k, v):
    """o [L, H, D] of the recurrence."""
    return lightning_with_state(q, k, v)[0]


# ------------------------------------------------------------ block sparse
def pooled_keys(k, sc: dict):
    """c [NK, Hkv, D]: the mean of every whole kernel of k [L, Hkv, D]."""
    import jax
    import jax.numpy as jnp
    kernel, stride = sc["kernel_size"], sc["kernel_stride"]
    L = k.shape[0]
    n = (L - kernel) // stride + 1 if L >= kernel else 0
    return jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(
        k, s, kernel, 0).mean(0))(jnp.arange(n) * stride)


def selected_blocks(q, c, t, L: int, sc: dict):
    """[Hkv, Q, NB] bool: the blocks each query q [Q, H, D] at positions
    `t` [Q] attends, one selection a KV group, from the pooled keys c
    [NK, Hkv, D] of a sequence of L positions."""
    import jax
    import jax.numpy as jnp
    block, kernel, stride = (sc["block_size"], sc["kernel_size"],
                             sc["kernel_stride"])
    Q, H, D = q.shape
    NK, Hkv, _ = c.shape
    NB = -(-L // block)
    b0 = jnp.arange(NB) * block
    forced = (b0[None, :] < sc["init_blocks"] * block) | (
        b0[None, :] + block - 1 >= t[:, None] - sc["window_size"] + 1)
    visible = b0[None, :] <= t[:, None]                        # [Q, NB]
    if NK:
        starts = jnp.arange(NK) * stride
        qg = q.reshape(Q, Hkv, H // Hkv, D)
        s = jnp.einsum("qhgd,jhd->hgqj", qg, c) / jnp.sqrt(float(D))
        seen = (starts[None, :] + kernel - 1 <= t[:, None])    # [Q, NK]
        s = jnp.where(seen, s, -jnp.inf)
        p = jnp.where(seen, jax.nn.softmax(
            jnp.where(seen.any(-1, keepdims=True), s, 0.0), axis=-1), 0.0)
        r = p.sum(1)                                           # [Hkv,Q,NK]
        touches = (starts[None, :] < b0[:, None] + block) & (
            starts[None, :] + kernel > b0[:, None])            # [NB, NK]
        R = jnp.max(jnp.where(touches, r[:, :, None, :], -jnp.inf), -1)
    else:
        R = jnp.zeros((Hkv, Q, NB), jnp.float32)
    score = jnp.where(visible, jnp.where(forced, jnp.inf, R), -jnp.inf)
    # each query's blocks ranked by falling score, ties to the lower block
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < sc["topk"]) & visible


def block_sparse(q, k, v, sc: dict, with_blocks: bool = False):
    """o [L, H, D]: each query over the positions up to its own of the
    blocks it selects, a block of queries at a time."""
    import jax
    import jax.numpy as jnp
    L, H, D = q.shape
    Hkv = k.shape[1]
    qb = next(b for b in (QUERY_BLOCK, 64, 32, 16, 8, 4, 2, 1) if L % b == 0)
    pos = jnp.arange(L)
    c = pooled_keys(k, sc)

    def some(i):
        t = i * qb + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        blocks = selected_blocks(qs, c, t, L, sc)              # [Hkv,qb,NB]
        mask = jnp.repeat(blocks, sc["block_size"], axis=-1)[..., :L] \
            & (pos[None, :] <= t[:, None])
        qg = qs.reshape(qb, Hkv, H // Hkv, D)
        s = jnp.einsum("qhgd,mhd->hgqm", qg, k) / jnp.sqrt(float(D))
        a = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hgqm,mhd->qhgd", a, v).reshape(qb, H, D)
        return (o, blocks) if with_blocks else o

    out = jax.lax.map(some, jnp.arange(L // qb))
    if with_blocks:
        return (out[0].reshape(L, H, D),
                out[1].transpose(1, 0, 2, 3).reshape(Hkv, L, -1))
    return out.reshape(L, H, D)


# ------------------------------------------------------------------ layers
_SHAPE_KEYS = ("rms_norm_eps", "rope_theta")


def _layer_fns(m):
    sc = m["sparse_config"]
    return _layer_fns_of(tuple(m[k] for k in _SHAPE_KEYS)
                         + (residual_scale(m),)
                         + tuple(sorted(sc.items())))


@functools.lru_cache(maxsize=None)
def _layer_fns_of(key):
    """Jitted pieces, one compile each per sequence length: the mixer of
    each kind with its residual, and the MLP with its."""
    import jax
    import jax.numpy as jnp
    eps, theta, c = key[:3]
    sc = dict(key[3:])

    def heads(u, w):
        return jnp.einsum("ld,dhk->lhk", u, w["kernel"])

    def close(x, o, u, a):
        o = o * jax.nn.sigmoid(heads(u, a["gate"]))
        return x + c * jnp.einsum("lhk,hkd->ld", o, a["o"]["kernel"])

    @jax.jit
    @_highest
    def lin(x, p, n):
        """-> (the layer's output, its state after the first n tokens)"""
        p = _f32(p)
        a = p["attn"]
        u = _rms(x, p["attn_norm"]["scale"], eps)
        q = _rope(_rms(heads(u, a["q"]), a["q_norm"]["scale"], eps), theta)
        k = _rope(_rms(heads(u, a["k"]), a["k_norm"]["scale"], eps), theta)
        o, state = lightning_with_state(q, k, heads(u, a["v"]), n)
        return close(x, _rms(o, a["o_norm"]["scale"], eps), u, a), state

    @jax.jit
    @_highest
    def blk(x, p):
        p = _f32(p)
        a = p["attn"]
        u = _rms(x, p["attn_norm"]["scale"], eps)
        q = _rms(heads(u, a["q"]), a["q_norm"]["scale"], eps)
        k = _rms(heads(u, a["k"]), a["k_norm"]["scale"], eps)
        return close(x, block_sparse(q, k, heads(u, a["v"]), sc), u, a)

    @jax.jit
    @_highest
    def mlp(x, p):
        p = _f32(p)
        w = p["mlp"]

        def some(xs):
            u = _rms(xs, p["mlp_norm"]["scale"], eps)
            return (jax.nn.silu(u @ w["gate"]["kernel"])
                    * (u @ w["up"]["kernel"])) @ w["down"]["kernel"]

        # a block of rows at a time: [8960, 16384] float32 three times
        # over would be the replica's peak of memory
        L = x.shape[0]
        rb = next(b for b in (ROW_BLOCK, 128, 64, 32, 16, 8, 4, 2, 1)
                  if L % b == 0)
        y = jax.lax.map(some, x.reshape(L // rb, rb, -1)).reshape(L, -1)
        return x + c * y

    return {"lightning-attn": lin, "minicpm4": blk}, mlp


def hidden_states(params, m: dict, tokens, states_after=None):
    """Final-norm hidden states [L, d] of one sequence `tokens` [L]; with
    `states_after` = n also each lightning layer's state [H, D, D] after
    the first n tokens, in the layers' order."""
    import jax.numpy as jnp
    mixers, mlp = _layer_fns(m)
    n = len(tokens) if states_after is None else states_after
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32) \
        * m["scale_emb"]
    states = []
    for i, kind in enumerate(m["mixer_types"]):
        p = params[f"layer_{i}"]
        if kind == "lightning-attn":
            x, state = mixers[kind](x, p, jnp.int32(n))
            states.append(state)
        else:
            x = mixers[kind](x, p)
        x = mlp(x, p)
    h = _rms(x, params["final_norm"]["scale"].astype(jnp.float32),
             m["rms_norm_eps"])
    return h if states_after is None else (h, states)


@_highest
def logits(params, m: dict, tokens):
    """[L, vocab] float32 next-token logits of one sequence."""
    import jax.numpy as jnp
    h = hidden_states(params, m, tokens) \
        / (m["hidden_size"] / m["dim_model_base"])
    return h @ params["unembed"].astype(jnp.float32)


@_highest
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


@_highest
def teacher_forced_gaps(params, m: dict, prompt, generated, pad_to=None,
                        with_spread=False, with_rows=False):
    """For each generated token: the largest reference logit at its
    position minus the reference logit of the token the system chose. One
    pass over prompt + generated; `pad_to` pads the sequence at its end
    (every layer is causal: later positions change no earlier one).
    `with_rows`: -> (gaps, the logits' spread, the reference's logits
    [len(generated), vocab] at the scored positions, each lightning
    layer's state after the last scored position)."""
    import jax.numpy as jnp
    import numpy as np
    seq = (list(prompt) + list(generated))[:-1]
    n = len(seq)
    seq = seq + [0] * max(0, (pad_to or 0) - n)
    h, states = hidden_states(params, m, seq, states_after=n)
    h = h[len(prompt) - 1:n] / (m["hidden_size"] / m["dim_model_base"])
    rows = h @ params["unembed"].astype(jnp.float32)
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(generated)[:, None], axis=-1)[:, 0]
    gaps = np.asarray(rows.max(-1) - chosen, np.float64).tolist()
    if with_rows or with_spread:
        spread = float(jnp.std(rows, axis=-1).mean())
        return (gaps, spread, rows, states) if with_rows else (gaps, spread)
    return gaps
