"""The plain reference of the `afmoe` family: Trinity-Large-Preview's
forward pass and next-token loss in straightforward jax.numpy
(`config.json`, and `transformers`' `modeling_afmoe.py` for what it does not
pin).

float32 throughout, `default_matmul_precision("highest")`, no cache, no
ring, no tiles, no kernel, no batching (one sequence at a time), no flax. A
layer i of kind `layer_types[i]`, on the residual h [L, 3072]:

    u      = RMSNorm(h)                                   (input norm)
    q,k,v  = W_q u [48 x 128], W_k u [8 x 128], W_v u [8 x 128]
    q, k   = RMSNorm a head (q_norm, k_norm)
    q, k   = rope(q, k), theta 10,000        "sliding_attention" ONLY; a
                                             "full_attention" layer has none
    a_t[j] = softmax_s(q_t[j] . k_s[j // 6] / sqrt(128)) v_s[j // 6]
             over s <= t, and in a sliding layer s > t - 4096: the 4,096
             newest positions, t's own among them. A PLAIN MASK on the
             full [rows, L] score matrix, a block of rows at a time
    a      = a * sigmoid(W_g u)                           (W_g 3072 x 6144)
    h      = h + RMSNorm(W_o a)                           (post-attn norm)
    m      = RMSNorm(h)                                   (pre-MLP norm)
    f      = SwiGLU_12288(m)                              i < num_dense_layers
    f      = shared(m) + sum_{e in top4} w_e expert_e(m)  otherwise, with
             s = sigmoid(m W_r) over all 256 experts, float32; the four are
             the largest of s + b (b the selection bias: it chooses and does
             not weigh); w = s at the four, divided by their sum
             (route_norm), times route_scale 2.448; `shared` a SwiGLU of
             3072 x num_shared_experts, each expert a SwiGLU of 3072
    h      = h + RMSNorm(f)                               (post-MLP norm)

    e      = embed[ids] * sqrt(3072)  (mup_enabled);  logits = W_head RMSNorm(h)

It reads the program's parameter tree (`embed`, `layer_<i>/{attn_norm,
attn, post_attn_norm, mlp_norm, mlp | moe, post_mlp_norm}`, `final_norm`,
`unembed`). It runs in the replica beside 12 GB held, so it upcasts ONE
matrix, and one expert, at a time, attends QUERY_BLOCK rows of the score
matrix at a time ([48, 64, 12,544] float32 is 0.15 GB), takes the dense
MLP ROW_BLOCK rows at a time, and unembeds only the scored positions.

Departures from the published description:
- the share of experts: `m["num_local_experts"]` experts from
  `experts_first(m)` on are held (one rank of `deployment.chips_per_layer`);
  the router, its bias, the top-4, the norm and the scale are the whole
  layer's, the shared expert is whole, and what the 224 absent experts would
  add is left out, here as in the program;
- the slice of the vocabulary: both tables hold `vocab_size` rows as cut
  (an eighth), ids are drawn from the slice, logits are over it;
- `n_group` = `topk_group` = 1: no grouping of experts, so none is written;
- the selection bias is a parameter of the tree here (`router_bias`), a
  buffer there: the same number either way.
"""

from __future__ import annotations

import functools
import math

QUERY_BLOCK = 64             # rows of the score matrix at a time
ROW_BLOCK = 1024             # rows the dense MLP takes at a time
SLIDING, FULL = "sliding_attention", "full_attention"


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        import jax
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def experts_first(m: dict) -> int:
    """The first expert this share holds."""
    return int((m.get("deployment") or {}).get("expert_rank", 0)) \
        * m["num_local_experts"]


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [L, H, D], positions 0..L-1, rotate-half."""
    import jax.numpy as jnp
    L, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def window_mask(t, L: int, window: int):
    """[len(t), L] bool: the positions s that a row at position t attends,
    s <= t and, where `window`, s > t - window."""
    import jax.numpy as jnp
    s = jnp.arange(L)[None, :]
    mask = s <= t[:, None]
    return mask & (s > t[:, None] - window) if window else mask


def _fns(m: dict):
    return _fns_of((m["rms_norm_eps"], m["num_key_value_heads"],
                    float(m["rope_theta"]), m["sliding_window"],
                    m["num_experts_per_tok"], experts_first(m),
                    m["num_local_experts"], bool(m.get("route_norm", True)),
                    float(m.get("route_scale", 1.0))))


@functools.lru_cache(maxsize=None)
def _fns_of(key):
    """Jitted pieces, one compile each a sequence length."""
    import jax
    import jax.numpy as jnp
    eps, Hkv, theta, window, top_k, first, held, route_norm, scale = key
    f32 = lambda w: w.astype(jnp.float32)                    # noqa: E731

    @jax.jit
    @_highest
    def norm(x, s):
        return _rms(x, s, eps)

    @jax.jit
    @_highest
    def dot(x, w):
        """x [L, d] through w [d, ..], or the heads x [L, H, D] through
        w [H, D, d]: ONE matrix upcast."""
        if x.ndim == 3:
            return jnp.einsum("lhk,hkd->ld", x, f32(w))
        return jnp.tensordot(x, f32(w), axes=([1], [0]))

    @functools.partial(jax.jit, static_argnums=(3,))
    @_highest
    def heads(q, k, p, rotary):
        q, k = _rms(q, p["q_norm"]["scale"], eps), \
            _rms(k, p["k_norm"]["scale"], eps)
        return (_rope(q, theta), _rope(k, theta)) if rotary else (q, k)

    @functools.partial(jax.jit, static_argnums=(4,))
    @_highest
    def attend(t0, q, k, v, sliding):
        """Rows t0 .. t0 + Q - 1 (q the block's; k, v the sequence's): the
        full score matrix of the block under the plain mask."""
        Q, H, D = q.shape
        L = k.shape[0]
        mask = window_mask(t0 + jnp.arange(Q), L, window if sliding else 0)
        qg = q.reshape(Q, Hkv, H // Hkv, D)     # head j reads KV head j // G
        s = jnp.einsum("qhgd,lhd->hgql", qg, k) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgql,lhd->qhgd", a, v).reshape(Q, H, D)

    @jax.jit
    @_highest
    def gated(a, g):
        return a * jax.nn.sigmoid(g)

    @jax.jit
    @_highest
    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ f32(gate)) * (x @ f32(up))) @ f32(down)

    @jax.jit
    @_highest
    def gates(normed, router, bias):
        """[L, held]: a token's weight for each held expert, 0 where it
        did not choose it."""
        s = jax.nn.sigmoid(normed @ f32(router))
        _, idx = jax.lax.top_k(s + f32(bias), top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if route_norm:
            w = w / w.sum(-1, keepdims=True)
        g = jnp.einsum("lk,lke->le", w * scale, jax.nn.one_hot(
            idx, s.shape[-1], dtype=jnp.float32))
        return g[:, first:first + held]

    @jax.jit
    @_highest
    def add_expert(acc, normed, gate_e, w_gate, w_up, w_down):
        y = (jax.nn.silu(normed @ f32(w_gate)) * (normed @ f32(w_up))) \
            @ f32(w_down)
        return acc + gate_e[:, None] * y

    return norm, dot, heads, attend, gated, swiglu, gates, add_expert


def attention(x, p, m: dict, kind: str):
    """One layer's attention branch before its output norm: [L, d] normed
    input -> [L, d]."""
    import jax.numpy as jnp
    _, dot, heads, attend, gated, *_ = _fns(m)
    L = x.shape[0]
    q, k, v, g = (dot(x, p[w]["kernel"]) for w in ("q", "k", "v", "gate"))
    q, k = heads(q, k, {n: p[n] for n in ("q_norm", "k_norm")},
                 kind == SLIDING)
    att = jnp.concatenate([
        attend(t0, q[t0:t0 + QUERY_BLOCK], k, v, kind == SLIDING)
        for t0 in range(0, L, QUERY_BLOCK)])
    return dot(gated(att, g), p["o"]["kernel"])


def expert_layer(normed, p, m: dict):
    """The shared expert and this share's routed experts: [L, d] -> [L, d]."""
    *_, swiglu, gates, add_expert = _fns(m)
    out = swiglu(normed, *(p[f"shared_{w}"]["kernel"]
                           for w in ("gate", "up", "down")))
    g = gates(normed, p["router"], p["router_bias"])
    for e in range(m["num_local_experts"]):
        out = add_expert(out, normed, g[:, e], p["gate"][e], p["up"][e],
                         p["down"][e])
    return out


def hidden_states(params, m: dict, tokens):
    """Final-norm hidden states [L, d] of one sequence `tokens` [L]."""
    import jax.numpy as jnp
    norm, *_, swiglu, _, _ = _fns(m)
    L = len(tokens)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    if m.get("mup_enabled"):
        x = x * math.sqrt(m["hidden_size"])
    for i, kind in enumerate(m["layer_types"]):
        p = params[f"layer_{i}"]
        att = attention(norm(x, p["attn_norm"]["scale"]), p["attn"], m, kind)
        x = x + norm(att, p["post_attn_norm"]["scale"])
        u = norm(x, p["mlp_norm"]["scale"])
        if i < m["num_dense_layers"]:
            w = [p["mlp"][n]["kernel"] for n in ("gate", "up", "down")]
            y = jnp.concatenate([swiglu(u[at:at + ROW_BLOCK], *w)
                                 for at in range(0, L, ROW_BLOCK)])
        else:
            y = expert_layer(u, p["moe"], m)
        x = x + norm(y, p["post_mlp_norm"]["scale"])
    return norm(x, params["final_norm"]["scale"])


def logits(params, m: dict, tokens, rows=None):
    """[L, vocab] float32 next-token logits of one sequence, or of its
    positions rows = (first, end) alone."""
    _, dot, *_ = _fns(m)
    h = hidden_states(params, m, tokens)
    if rows is not None:
        h = h[rows[0]:rows[1]]
    return dot(h, params["unembed"])


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
                        with_spread=False, with_rows=False):
    """For each generated token: the largest reference logit at its
    position minus the reference logit of the token the system chose. One
    pass over prompt + generated; `pad_to` pads the sequence at its end
    (every layer is causal: later positions change no earlier one).
    `with_rows`: -> {"gaps", "spread" (the logits'), "rows" (the
    reference's logits [len(generated), vocab] at the scored positions)}."""
    import jax.numpy as jnp
    import numpy as np
    _, dot, *_ = _fns(m)
    seq = (list(prompt) + list(generated))[:-1]
    n = len(seq)
    seq = seq + [0] * max(0, (pad_to or 0) - n)
    rows = dot(hidden_states(params, m, seq)[len(prompt) - 1:n],
               params["unembed"])
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(generated)[:, None], axis=-1)[:, 0]
    gaps = np.asarray(rows.max(-1) - chosen, np.float64).tolist()
    if not (with_rows or with_spread):
        return gaps
    spread = float(jnp.std(rows, axis=-1).mean())
    if with_rows:
        return {"gaps": gaps, "spread": spread, "rows": rows}
    return gaps, spread
