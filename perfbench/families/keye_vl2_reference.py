"""The plain reference of the `keye_vl2` family: the language model of
Keye-VL-2.0-30B-A3B, its forward pass and next-token loss in
straightforward jax.numpy.

float32 throughout, `default_matmul_precision("highest")`, no cache, no
kernel, no batching (one sequence at a time), no flax. A layer, with `x_t`
its RMS-normed input at position t:

    q_t  = rope(norm_q(W_q x_t))   [32 x 128]   (RMSNorm on each head)
    k_t  = rope(norm_k(W_k x_t))   [4 x 128]
    v_t  = W_v x_t                 [4 x 128]
    qI_t = rope(W_qI x_t)          [16 x 64]    the indexer
    kI_s = rope(LN(W_kI x_s))      [64]         one indexer key a position
    w_t  = W_w x_t                 [16]
    I(t, s) = sum_j w_t[j] relu(qI_t[j] . kI_s)             for s <= t
    S_t  = the min(topk, t + 1) positions s <= t of largest I(t, s),
           ties to the lower position
    o_t[h] = sum_{s in S_t} softmax_s(q_t[h] . k_s[g(h)] / sqrt(128)) v_s[g(h)]

then W_o, the residual, and the expert layer: a float32 softmax over all
the router's outputs, the top `num_experts_per_tok`, their gates
renormalised to sum to one, SwiGLU experts (DeepSeek-V3.2-Exp's published
equations 1 and 2 for the indexer; the Qwen3-MoE trunk for the rest).

It reads the program's parameter tree (`embed`, `layers/block/...` stacked
over layers, `final_norm`, `unembed`) and takes one layer, and one expert,
out of it at a time, upcasting only that slice; the index scores, the
selection and the attention run a block of queries at a time, so that 12k
tokens fit beside the served weights on one chip.

Departures from the published model:
- text positions only: the three components of an M-RoPE position are
  equal, so M-RoPE is the 1-D rotary (rotate-half layout);
- the share of experts: `m["num_local_experts"]` experts from
  `experts_first(m)` on are held (one rank of `deployment.chips_per_layer`);
  the router, the top-k and the gates are the whole layer's, and what the
  absent experts would add is left out, here as in the program;
- positive scale factors on `w_t` (1/sqrt(heads), 1/sqrt(head size)) change
  no selection and are left out; `q_chunk_size` / `kv_chunk_size` block the
  score computation and change no result.
"""

from __future__ import annotations

import functools

QUERY_BLOCK = 256


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
        * scale


def _layer_norm(x, scale, bias, eps):
    import jax
    import jax.numpy as jnp
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale + bias


def _rope(x, theta):
    """x [L, H, D], positions 0..L-1, rotate-half."""
    import jax.numpy as jnp
    L, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, gate, up, down):
    import jax
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _f32(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def selected(scores, topk: int):
    """scores [Q, L], -inf where s > t: the mask [Q, L] of S_t. A stable
    sort by falling score puts equal scores in rising position, so the
    first topk of it are the set with ties to the lower position."""
    import jax.numpy as jnp
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :topk]
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], order].set(True)
    return mask & (scores > -jnp.inf)


def _layer_fns(m):
    sa = m["sa_config"]
    return _layer_fns_of((
        m["rms_norm_eps"], m["num_attention_heads"],
        m["num_key_value_heads"], float(m["rope_theta"]),
        m["num_experts_per_tok"], experts_first(m),
        m["num_local_experts"], sa["topk"]))


@functools.lru_cache(maxsize=None)
def _layer_fns_of(key):
    """Jitted pieces, one compile each per sequence length."""
    import jax
    import jax.numpy as jnp
    eps, H, Hkv, theta, top_k, first, held, topk = key

    @jax.jit
    @_highest
    def project(x, p):
        """x [L, d] -> the layer's normed input's projections."""
        p = _f32(p)
        a = p["attn"]
        xn = _rms(x, p["attn_norm"]["scale"], eps)
        q = jnp.einsum("ld,dhk->lhk", xn, a["q"]["kernel"])
        k = jnp.einsum("ld,dhk->lhk", xn, a["k"]["kernel"])
        q = _rope(_rms(q, a["q_norm"]["scale"], eps), theta)
        k = _rope(_rms(k, a["k_norm"]["scale"], eps), theta)
        v = jnp.einsum("ld,dhk->lhk", xn, a["v"]["kernel"])
        qi = _rope(jnp.einsum("ld,djk->ljk", xn, a["index_q"]["kernel"]),
                   theta)
        ki = _layer_norm(xn @ a["index_k"]["kernel"],
                         a["index_k_norm"]["scale"],
                         a["index_k_norm"]["bias"], eps)
        ki = _rope(ki[:, None, :], theta)[:, 0]
        w = xn @ a["index_w"]["kernel"]
        return q, k, v, qi, ki, w

    @jax.jit
    @_highest
    def attend(t0, q, k, v, qi, ki, w):
        """The attention output [Q, H, D] of queries t0 .. t0 + Q - 1
        (q, qi, w are the block's; k, v, ki the whole sequence's)."""
        Q, L = q.shape[0], k.shape[0]
        t = t0 + jnp.arange(Q)
        causal = jnp.arange(L)[None, :] <= t[:, None]
        per_head = jax.nn.relu(jnp.einsum("qjk,lk->qjl", qi, ki))
        scores = jnp.einsum("qj,qjl->ql", w, per_head)
        mask = selected(jnp.where(causal, scores, -jnp.inf), topk)
        kr = jnp.repeat(k, H // Hkv, axis=1)   # each KV head serves a group
        vr = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("qhk,lhk->hql", q, kr) / jnp.sqrt(float(q.shape[-1]))
        a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hql,lhk->qhk", a, vr), mask

    @jax.jit
    @_highest
    def close(x, att, p):
        p = _f32(p)
        h = x + jnp.einsum("lhk,hkd->ld", att, p["attn"]["o"]["kernel"])
        return h, _rms(h, p["mlp_norm"]["scale"], eps)

    @jax.jit
    @_highest
    def gates(normed, router):
        probs = jax.nn.softmax(normed @ router.astype(jnp.float32), -1)
        top, idx = jax.lax.top_k(probs, top_k)
        top = top / top.sum(-1, keepdims=True)
        # [L, E]: a token's gate for each expert, 0 where not routed;
        # then the held experts' columns
        g = jnp.einsum("lk,lke->le", top, jax.nn.one_hot(
            idx, probs.shape[-1], dtype=jnp.float32))
        return g[:, first:first + held]

    @jax.jit
    @_highest
    def add_expert(acc, normed, gate_e, w_gate, w_up, w_down):
        y = _swiglu(normed, w_gate.astype(jnp.float32),
                    w_up.astype(jnp.float32), w_down.astype(jnp.float32))
        return acc + gate_e[:, None] * y

    return project, attend, close, gates, add_expert


def layer(x, p, m: dict, with_masks: bool = False):
    """One layer [L, d] -> [L, d]; `p` is the layer's slice of the tree
    (`attn`, `attn_norm`, `mlp_norm`, `moe`)."""
    import jax.numpy as jnp
    project, attend, close, gates, add_expert = _layer_fns(m)
    L = x.shape[0]
    q, k, v, qi, ki, w = project(x, {n: p[n] for n in (
        "attn", "attn_norm")})
    att, masks = [], []
    for t0 in range(0, L, QUERY_BLOCK):
        blk = slice(t0, min(L, t0 + QUERY_BLOCK))
        o, mask = attend(t0, q[blk], k, v, qi[blk], ki, w[blk])
        att.append(o)
        masks.append(mask)
    h, normed = close(x, jnp.concatenate(att), {n: p[n] for n in (
        "attn", "mlp_norm")})
    g = gates(normed, p["moe"]["router"])
    out = h
    for e in range(m["num_local_experts"]):
        out = add_expert(out, normed, g[:, e], p["moe"]["gate"][e],
                         p["moe"]["up"][e], p["moe"]["down"][e])
    if with_masks:
        return out, jnp.concatenate(masks)
    return out


def hidden_states(params, m: dict, tokens):
    """Final-norm hidden states [L, d] of one sequence `tokens` [L]."""
    import jax
    import jax.numpy as jnp
    block = params["layers"]["block"]
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["num_hidden_layers"]):
        x = layer(x, jax.tree.map(lambda a: a[i], block), m)
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
    """Mean next-token cross-entropy of one sequence [L + 1]."""
    import jax
    import jax.numpy as jnp
    tokens = jnp.asarray(tokens)
    lg = logits(params, m, tokens[:-1])
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def batch_loss(params, m: dict, batch):
    """The training step's loss on `batch` [B, L + 1], sequence by
    sequence."""
    return sum(float(sequence_loss(params, m, row)) for row in batch) \
        / len(batch)


@_highest
def teacher_forced_gaps(params, m: dict, prompt, generated, pad_to=None,
                        with_spread=False):
    """For each generated token: the largest reference logit at its
    position minus the reference logit of the token the system chose (0
    where the system's greedy choice is the reference's argmax). One pass
    over prompt + generated; `pad_to` pads the sequence at its end (every
    position attends and selects among earlier ones only, so later
    positions change no earlier one). Only the scored rows meet the
    unembedding: [L, vocab] float32 at 12k tokens would be 7 GB."""
    import jax.numpy as jnp
    import numpy as np
    seq = (list(prompt) + list(generated))[:-1]
    n = len(seq)
    seq = seq + [0] * max(0, (pad_to or 0) - n)
    rows = hidden_states(params, m, seq)[len(prompt) - 1:n] \
        @ params["unembed"].astype(jnp.float32)
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(generated)[:, None], axis=-1)[:, 0]
    gaps = np.asarray(rows.max(-1) - chosen, np.float64).tolist()
    if with_spread:
        # the logits' standard deviation over the vocabulary, for scale
        return gaps, float(jnp.std(rows, axis=-1).mean())
    return gaps
