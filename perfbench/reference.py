"""The plain reference: Mistral and Mixtral forward passes and the
next-token loss, in straightforward jax.numpy.

float32 throughout, `default_matmul_precision("highest")`, no cache, no
batching (one sequence at a time), no kernel, no scan, no flax: the
published equations written out. RMSNorm, rotary embeddings (rotate-half
layout, as the published checkpoints are laid out), grouped-query causal
attention, SwiGLU; for Mixtral a softmax router over all experts, the
top-k gates renormalised to sum to one (equal to the softmax over the
top-k logits) and no token dropped. It reads the program's parameter
tree (`embed`, `layers/block/...` stacked over layers, `final_norm`,
`unembed`) and takes one layer, and one expert, out of it at a time,
upcasting only that slice, so that it fits beside the served weights on
one chip at the full widths.

Departures from the published models, all inert at the sizes run here:
no sliding window (sequences stay below Mistral's 4096), no attention
dropout, no router jitter.
"""

from __future__ import annotations

import functools


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


def _attention(x, p, m):
    """x [L, d] -> [L, d]; p: this layer's q, k, v, o kernels (float32)."""
    import jax
    import jax.numpy as jnp
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    L = x.shape[0]
    q = _rope(jnp.einsum("ld,dhk->lhk", x, p["q"]), m["rope_theta"])
    k = _rope(jnp.einsum("ld,dhk->lhk", x, p["k"]), m["rope_theta"])
    v = jnp.einsum("ld,dhk->lhk", x, p["v"])
    k = jnp.repeat(k, H // Hkv, axis=1)       # each KV head serves a group
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhk,mhk->hqm", q, k) / jnp.sqrt(float(q.shape[-1]))
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("lhk,hkd->ld", jnp.einsum("hqm,mhk->qhk", a, v),
                      p["o"])


def _swiglu(x, gate, up, down):
    import jax
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _f32(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


_SHAPE_KEYS = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
               "rope_theta", "num_experts_per_tok")


def _layer_fns(m):
    return _layer_fns_of(tuple((k, m.get(k)) for k in _SHAPE_KEYS))


@functools.lru_cache(maxsize=None)
def _layer_fns_of(items):
    """Jitted pieces, one compile each per sequence length: everything of
    a layer but the experts, and one expert's contribution."""
    import jax
    import jax.numpy as jnp
    m = dict(items)
    eps = m["rms_norm_eps"]

    @jax.jit
    @_highest
    def attn_part(x, p):
        p = _f32(p)
        h = x + _attention(_rms(x, p["attn_norm"]["scale"], eps),
                           {n: p["attn"][n]["kernel"] for n in "qkvo"}, m)
        return h, _rms(h, p["mlp_norm"]["scale"], eps)

    @jax.jit
    @_highest
    def dense_mlp(h, normed, p):
        p = _f32(p)
        return h + _swiglu(normed, p["gate"]["kernel"], p["up"]["kernel"],
                           p["down"]["kernel"])

    @jax.jit
    @_highest
    def gates(normed, router):
        probs = jax.nn.softmax(normed @ router.astype(jnp.float32), -1)
        top, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
        top = top / top.sum(-1, keepdims=True)
        # [L, E]: a token's gate for each expert, 0 where not routed
        return jnp.einsum("lk,lke->le", top, jax.nn.one_hot(
            idx, probs.shape[-1], dtype=jnp.float32))

    @jax.jit
    @_highest
    def add_expert(acc, normed, gate_e, w_gate, w_up, w_down):
        y = _swiglu(normed, w_gate.astype(jnp.float32),
                    w_up.astype(jnp.float32), w_down.astype(jnp.float32))
        return acc + gate_e[:, None] * y

    return attn_part, dense_mlp, gates, add_expert


def hidden_states(params, m: dict, tokens):
    """Final-norm hidden states [L, d] of one sequence `tokens` [L]."""
    import jax
    import jax.numpy as jnp
    attn_part, dense_mlp, gates, add_expert = _layer_fns(m)
    block = params["layers"]["block"]
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for layer in range(m["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[layer], {
            k: v for k, v in block.items() if k not in ("mlp", "moe")})
        h, normed = attn_part(x, p)
        if "mlp" in block:
            x = dense_mlp(h, normed,
                          jax.tree.map(lambda a: a[layer], block["mlp"]))
        else:
            moe = block["moe"]
            g = gates(normed, moe["router"][layer])
            x = h
            for e in range(m["num_local_experts"]):
                x = add_expert(x, normed, g[:, e], moe["gate"][layer, e],
                               moe["up"][layer, e], moe["down"][layer, e])
    return _rms(x, params["final_norm"]["scale"].astype(jnp.float32),
                m["rms_norm_eps"])


@_highest
def logits(params, m: dict, tokens):
    """[L, vocab] float32 next-token logits of one sequence."""
    import jax.numpy as jnp
    return hidden_states(params, m, tokens) \
        @ params["unembed"].astype(jnp.float32)


@_highest
def sequence_loss(params, m: dict, tokens):
    """Mean next-token cross-entropy of one sequence [L + 1]: positions
    0..L-1 predict tokens 1..L."""
    import jax
    import jax.numpy as jnp
    tokens = jnp.asarray(tokens)
    lg = logits(params, m, tokens[:-1])
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def batch_loss(params, m: dict, batch):
    """The training step's loss on `batch` [B, L + 1], sequence by
    sequence (every sequence has the same number of targets)."""
    return sum(float(sequence_loss(params, m, row)) for row in batch) \
        / len(batch)


def teacher_forced_gaps(params, m: dict, prompt, generated, pad_to=None,
                        with_spread=False):
    """For each generated token: the largest reference logit at its
    position minus the reference logit of the token the system chose
    (0 where the system's greedy choice is the reference's argmax). One
    pass over prompt + generated; position len(prompt) - 1 + i scores
    generated[i]. `pad_to` pads the sequence at its end (causal attention:
    later positions change no earlier one), so that sequences of several
    lengths share one compiled program."""
    import jax.numpy as jnp
    import numpy as np
    seq = (list(prompt) + list(generated))[:-1]
    n = len(seq)
    seq = seq + [0] * max(0, (pad_to or 0) - n)
    lg = logits(params, m, seq)
    rows = lg[len(prompt) - 1:n]
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(generated)[:, None], axis=-1)[:, 0]
    gaps = np.asarray(rows.max(-1) - chosen, np.float64).tolist()
    if with_spread:
        # the logits' standard deviation over the vocabulary, for scale
        return gaps, float(jnp.std(rows, axis=-1).mean())
    return gaps
