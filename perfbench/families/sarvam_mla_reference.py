"""The plain reference of the `sarvam_mla` family: sarvam-105b's forward pass
and next-token loss in straightforward jax.numpy (`config.json`,
`model_type` `sarvam_mla`; DeepSeek-V2's `modeling_deepseek.py` for the
latent attention and the YaRN rotary, DeepSeek-V3's for the router, which
are where the config's keys come from).

float32 throughout, `default_matmul_precision("highest")`, the EXPANDED form
of the attention only (each head's K and V made from the latent, a plain
causal mask on the score matrix), no cache, no tiles, no absorbed form, no
kernel, no batching (one sequence at a time), no flax. Layer i on the
residual h [L, 4096]:

    u        = RMSNorm(h)                                 (input norm)
    q        = W_q u  [64 x 192]        no q_lora_rank: straight from u
    q        = RMSNorm a head over its 192  (use_qk_norm; ASSUMED, below)
    q_r      = rope(q[..., 128:])                        the last 64 alone
    [c ‖ k_r] = W_dkv u  [512 + 64];  c = RMSNorm(c) (kv_a_layernorm);
    k_r      = rope(k_r)             ONE key of 64 a position, every head's
    k[j]     = [W_uk,j c ‖ k_r] (192),   v[j] = W_uv,j c (128)
    a_t[j]   = softmax_{s<=t}(scale q_t[j] . k_s[j]) v_s[j]
               scale = 192^-1/2 * m^2, m = 0.1 mscale_all_dim ln(factor) + 1
    h        = h + W_o a                                  (8192 -> 4096)
    g        = RMSNorm(h)                                 (post-attn norm)
    f        = SwiGLU_16384(g)                       i < first_k_dense_replace
    f        = shared(g) + sum_{e in top8} w_e expert_e(g)  otherwise, with
               s = sigmoid(g W_r) over all 128 experts, float32; the eight
               are the largest of s + b (b the selection bias,
               moe_router_enable_expert_bias: it chooses and does not
               weigh); w = s at the eight, divided by their sum, times
               routed_scaling_factor 2.5; `shared` and each expert a SwiGLU
               of 2048
    h        = h + f

    rope: YaRN (`deepseek_yarn`) over the 64 rotary dimensions, theta
    10,000: each frequency a blend of itself and itself / factor by the
    linear ramp between the correction dimensions of beta_fast and
    beta_slow over original_max_position_embeddings (`yarn_inv_freq`); cos
    and sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim),
    which is 1 here.

    logits   = W_head RMSNorm(h)

It reads the program's parameter tree (`embed`, `layer_<i>/{attn_norm, attn
{q, q_norm, kv_down, kv_norm, kv_up, o}, mlp_norm, mlp | moe}`,
`final_norm`, `unembed`). It runs in the replica beside 11 GB held, so it
upcasts ONE matrix, and one expert, at a time, takes HEAD_BLOCK heads and
QUERY_BLOCK rows of the score matrix at a time ([16, 128, 9,472] float32 is
78 MB; q, K and V of all 64 heads of 9,472 positions would be 1.2 GB), the
dense MLP ROW_BLOCK rows at a time, and unembeds only the scored positions.

Departures from the published description:
- the share of experts: `m["num_local_experts"]` experts from
  `experts_first(m)` on are held (one rank of `deployment.chips_per_layer`);
  the router, its bias, the top-8, the norm and the scale are the whole
  layer's, the shared expert is whole, and what the 112 absent experts would
  add is left out, here as in the program;
- the slice of the vocabulary: both tables hold `vocab_size` rows as cut (an
  eighth), ids are drawn from the slice, logits are over it;
- rotate-half on the rotary dimensions as they lie: DeepSeek's rotary pairs
  interleaved dimensions and de-interleaves q_r and k_r before rotate-half,
  which on seeded weights is a fixed permutation of W_q's and W_dkv's rotary
  columns: stated, not built;
- ASSUMED, where `config.json` does not pin a form (the configuration's
  `assumed` says why): `use_qk_norm` as an RMSNorm on each query head over
  its 192 before the rotary, and on the latent (not on expanded keys, which
  no server could attend absorbed); the sigmoid score function and the
  normalisation of the chosen gates (DeepSeek-V3's convention, with which
  the bias and the scaling factor come); no grouping of experts (no
  `n_group` key);
- the selection bias is a parameter of the tree here (`router_bias`), a
  buffer there: the same number either way.
"""

from __future__ import annotations

import functools
import math

QUERY_BLOCK = 128            # rows of the score matrix at a time
HEAD_BLOCK = 16              # heads at a time
ROW_BLOCK = 1024             # rows the dense MLP takes at a time


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


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(m: dict) -> float:
    """q_head_dim^-1/2, times mscale(factor, mscale_all_dim)^2 under YaRN
    (DeepSeek-V2's attention: only where mscale_all_dim is not 0)."""
    rs = m.get("rope_scaling") or {}
    mm = yarn_mscale(rs["factor"], rs["mscale_all_dim"]) \
        if rs.get("mscale_all_dim") else 1.0
    return m["q_head_dim"] ** -0.5 * mm * mm


def yarn_inv_freq(dim: int, base: float, rs):
    """The rotary's dim / 2 inverse frequencies, float32: plain where `rs`
    (the config's `rope_scaling`) is None, else YaRN's blend as
    `DeepseekV2YarnRotaryEmbedding` computes it."""
    import jax.numpy as jnp
    extra = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not rs:
        return extra
    inter = extra / rs["factor"]

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp                    # 1: the frequency is kept
    return inter * (1.0 - mask) + extra * mask


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, inv_freq, factor):
    """x [L, .., D], positions 0..L-1 on the first axis, rotate-half."""
    import jax.numpy as jnp
    L, D = x.shape[0], x.shape[-1]
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((L,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fns(m: dict):
    rs = m.get("rope_scaling")
    return _fns_of((
        m["rms_norm_eps"], float(m["rope_theta"]),
        tuple(sorted(rs.items())) if rs else None, m["kv_lora_rank"],
        m["qk_nope_head_dim"], m["qk_rope_head_dim"], softmax_scale(m),
        m["num_experts_per_tok"], experts_first(m), m["num_local_experts"],
        float(m["routed_scaling_factor"]),
        bool(m.get("use_qk_norm", False))))


@functools.lru_cache(maxsize=None)
def _fns_of(key):
    """Jitted pieces, one compile each a sequence length."""
    import jax
    import jax.numpy as jnp
    (eps, theta, rs, R, Dn, Dr, scale, top_k, first, held, route_scale,
     qk_norm) = key
    rs = dict(rs) if rs else None
    cos_factor = yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"]) if rs else 1.0
    f32 = lambda w: w.astype(jnp.float32)                    # noqa: E731

    @jax.jit
    @_highest
    def norm(x, s):
        return _rms(x, s, eps)

    @jax.jit
    @_highest
    def dot(x, w):
        """x [L, d] through w [d, ..], or heads x [L, H, D] through
        w [H, D, d]: ONE matrix upcast."""
        if x.ndim == 3:
            return jnp.einsum("lhk,hkd->ld", x, f32(w))
        return jnp.tensordot(x, f32(w), axes=([1], [0]))

    @jax.jit
    @_highest
    def latent(ckr, kv_norm):
        """[L, R + Dr] as projected -> the normed latent [L, R] and the
        rotated key [L, Dr]."""
        inv = yarn_inv_freq(Dr, theta, rs)
        return _rms(ckr[:, :R], kv_norm, eps), \
            _rope(ckr[:, R:], inv, cos_factor)

    @jax.jit
    @_highest
    def heads(q, q_norm, c, k_r, w_ukv):
        """A block of heads: q [L, h, Dn + Dr] as projected, the latent and
        the rotated key, w_ukv [R, h, Dn + Dv] -> q, k [L, h, Dn + Dr] and
        v [L, h, Dv]."""
        if qk_norm:
            q = _rms(q, q_norm, eps)
        q = jnp.concatenate([q[..., :Dn], _rope(
            q[..., Dn:], yarn_inv_freq(Dr, theta, rs), cos_factor)], -1)
        kv = jnp.einsum("lr,rhd->lhd", c, f32(w_ukv))
        k = jnp.concatenate([kv[..., :Dn], jnp.broadcast_to(
            k_r[:, None, :], kv.shape[:2] + (Dr,))], -1)
        return q, k, kv[..., Dn:]

    @jax.jit
    @_highest
    def attend(t0, q, k, v):
        """Rows t0 .. t0 + Q - 1 (q the block's; k, v the sequence's): the
        full score matrix of the block under the plain causal mask."""
        Q, L = q.shape[0], k.shape[0]
        mask = jnp.arange(L)[None, :] <= (t0 + jnp.arange(Q))[:, None]
        s = jnp.einsum("qhd,lhd->hql", q, k) * scale
        a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hql,lhd->qhd", a, v)

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
        w = w / w.sum(-1, keepdims=True)
        g = jnp.einsum("lk,lke->le", w * route_scale, jax.nn.one_hot(
            idx, s.shape[-1], dtype=jnp.float32))
        return g[:, first:first + held]

    @jax.jit
    @_highest
    def add_expert(acc, normed, gate_e, w_gate, w_up, w_down):
        y = (jax.nn.silu(normed @ f32(w_gate)) * (normed @ f32(w_up))) \
            @ f32(w_down)
        return acc + gate_e[:, None] * y

    return norm, dot, latent, heads, attend, swiglu, gates, add_expert


def attention(x, p, m: dict):
    """One layer's attention branch: [L, d] normed input -> [L, d]."""
    import jax.numpy as jnp
    _, dot, latent, heads, attend, *_ = _fns(m)
    L, H = x.shape[0], m["num_attention_heads"]
    c, k_r = latent(dot(x, p["kv_down"]["kernel"]), p["kv_norm"]["scale"])
    q_norm = p["q_norm"]["scale"] if "q_norm" in p else None
    out = 0.0
    for h0 in range(0, H, HEAD_BLOCK):
        of = slice(h0, h0 + HEAD_BLOCK)
        q, k, v = heads(dot(x, p["q"]["kernel"][:, of]), q_norm, c, k_r,
                        p["kv_up"][:, of])
        att = jnp.concatenate([
            attend(t0, q[t0:t0 + QUERY_BLOCK], k, v)
            for t0 in range(0, L, QUERY_BLOCK)])
        out = out + dot(att, p["o"]["kernel"][of])
    return out


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
    for i in range(m["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        x = x + attention(norm(x, p["attn_norm"]["scale"]), p["attn"], m)
        u = norm(x, p["mlp_norm"]["scale"])
        if i < m["first_k_dense_replace"]:
            w = [p["mlp"][n]["kernel"] for n in ("gate", "up", "down")]
            x = x + jnp.concatenate([swiglu(u[at:at + ROW_BLOCK], *w)
                                     for at in range(0, L, ROW_BLOCK)])
        else:
            x = x + expert_layer(u, p["moe"], m)
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
