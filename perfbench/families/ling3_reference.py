"""The plain reference of the `ling3` family: the language model of
Ling-3.0-flash-VL (inclusionAI; `config.json`) as one chip of its
deployment holds it, forward pass and next-token loss in straightforward
jax.numpy. The linear layers are Kimi Delta Attention as published (Kimi
Linear, arXiv:2510.26692), the latent layers DeepSeek-V2's attention without
`q_lora_rank`, the router DeepSeek-V3's with groups: those are where the
config's keys come from.

float32 throughout, `default_matmul_precision("highest")`; the KDA
recurrence ROW BY ROW (a `lax.scan` over the rows, no chunk); a plain masked
softmax over each head's expanded keys and values for the latent layers; the
router in groups by sorting; every expert the chip holds over every row; no
cache, no tile, no kernel, no flax, one sequence at a time. Layer i on the
residual h [L, 2560], u = RMSNorm(h):

  a KDA layer (published layer l where (l + 1) % 6 != 0), 32 heads of 128:
    q, k, v = silu(conv4(W_q u)), silu(conv4(W_k u)), silu(conv4(W_v u))
              conv4: depthwise, causal, 4 taps (the last on the row itself),
              no bias
    q = q / |q| * 128^-1/2,  k = k / |k|                           (a head)
    g = -5 sigmoid(exp(A_h) (W_a u + b))    the log-decay a CHANNEL, (-5, 0)
    beta = sigmoid(W_beta u)                                     one a head
    S' = Diag(exp(g_t)) S_{t-1};  x = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t x^T;  o_t = S_t^T q_t          S [128, 128] a head, from 0
    mixer = W_o (RMSNorm_128(o_t) * sigmoid(W_g u))     the gate ONE a head
  a latent layer ((l + 1) % 6 == 0), 32 heads:
    q = W_q u [32 x 192], RMSNorm a head over its 192 (use_qk_norm: ASSUMED)
    [c ‖ k_r] = W_dkv u [512 + 64]; c = RMSNorm(c); rotary (theta 6e6,
    plain) on k_r and on the last 64 of each query
    k[j] = [W_uk,j c ‖ k_r], v[j] = W_uv,j c (128)
    a_t[j] = softmax_{s<=t}(192^-1/2 q_t[j] . k_s[j]) v_s[j];  mixer = W_o a
  h = h + mixer;  g = RMSNorm(h)
  f = SwiGLU_6144(g)                               i < first_k_dense_replace
  f = shared(g) + sum_{e taken} w_e expert_e(g)    otherwise: s = sigmoid(g
      W_r) over all 512 experts; the experts lie in 8 groups of 64; a group's
      score is the sum of its two largest s + b; the 4 best groups stay; of
      their 256 experts the 8 of largest s + b are taken (b chooses and does
      not weigh); w = s at the taken, divided by their sum, times 2.5;
      `shared` and each expert a SwiGLU of 768
  h = h + f
  logits = W_head RMSNorm(h)

It reads the program's parameter tree (`embed`, `layer_<i>/{attn_norm, attn
{q, k, v, conv_w, g, A_log, g_bias, beta, gate, o_norm, o} | {q, q_norm,
kv_down, kv_norm, kv_up, o}, mlp_norm, mlp | moe}`, `final_norm`,
`unembed`). It runs in the replica beside 12 GB held, so it upcasts ONE
matrix, and one expert, at a time, takes HEAD_BLOCK heads and QUERY_BLOCK
rows of a latent layer's score matrix at a time, the dense MLP ROW_BLOCK
rows at a time, and unembeds only the scored positions.

Departures from the published description:
- the share of layers and experts: the chip's 13 layers are published
  layers 1-13 (`deployment.stage_layers`), so layer i here is published
  layer i + 1 and is a latent layer where (i + 2) % 6 == 0 (`kinds`);
  `num_local_experts` experts from `experts_first(m)` on are held (one rank
  of eight, exactly routing group 2); the router, its bias, the groups, the
  top-8, the norm and the scale are the whole layer's, the shared expert is
  whole, and what the 448 absent experts would add is left out, here as in
  the program;
- the slice of the vocabulary: both tables hold `vocab_size` rows as cut (an
  eighth); ids are drawn from the slice, logits are over it;
- the clamped SwiGLU (`*_swiglu_limit_list`) is not built: every layer held
  has limit 0 (no clamp), and a configuration that held one is refused;
- rotate-half on the rotary dimensions as they lie (families/
  sarvam_mla_reference.py says why that is a fixed permutation of columns
  on seeded weights);
- ASSUMED, where `config.json` does not pin a form (the configuration's
  `assumed` says why each): the layers' arrangement; KDA's order conv ->
  SiLU -> L2 norm, the 128^-1/2 on q, A a head and b a channel, the gate's
  form under `kda_safe_gate`, the output norm over a head's 128, the gate a
  head on the KDA output and none on a latent layer's, no rotary in a KDA
  layer; `use_qk_norm` as sarvam-105b's configuration reads it; a group's
  score as the sum of its two largest;
- the selection bias is a parameter of the tree here (`router_bias`), a
  buffer there: the same number either way.
"""

from __future__ import annotations

import functools

QUERY_BLOCK = 128            # rows of a latent layer's score matrix at a time
HEAD_BLOCK = 16              # heads at a time
ROW_BLOCK = 1024             # rows the dense MLP takes at a time


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        import jax
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def kinds(m: dict):
    """The kind of each layer held, by the published rule on the published
    layer's number: "mla" where (l + 1) % layer_group_size == 0."""
    first = (m.get("deployment") or {}).get("stage_layers", [0])[0]
    return ["mla" if (first + i + 1) % m["layer_group_size"] == 0 else "kda"
            for i in range(m["num_hidden_layers"])]


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
    """x [L, .., D], positions 0..L-1 on the first axis, rotate-half."""
    import jax.numpy as jnp
    L, D = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((L,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fns(m: dict):
    return _fns_of((
        m["rms_norm_eps"], float(m["rope_theta"]), m["kv_lora_rank"],
        m["qk_nope_head_dim"], m["qk_rope_head_dim"],
        m["num_attention_heads"], m["head_dim"],
        float(m["kda_lower_bound"]), m["num_experts_per_tok"],
        m["n_group"], m["topk_group"], experts_first(m),
        m["num_local_experts"], float(m["routed_scaling_factor"]),
        bool(m.get("use_qk_norm", False)), bool(m["norm_topk_prob"])))


@functools.lru_cache(maxsize=None)
def _fns_of(key):
    """Jitted pieces, one compile each a sequence length."""
    import jax
    import jax.numpy as jnp
    (eps, theta, R, Dn, Dr, H, D, floor, top_k, n_group, topk_group, first,
     held, route_scale, qk_norm, norm_topk) = key
    scale = (Dn + Dr) ** -0.5
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
    def conv_silu(x, w):
        """x [L, C] through the depthwise causal convolution of taps w
        [4, C] (the last on the row itself), from nothing, then SiLU."""
        L, taps = x.shape[0], w.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, x.shape[1]), jnp.float32), x])
        return jax.nn.silu(sum(f32(w)[j] * padded[j:j + L]
                               for j in range(taps)))

    @jax.jit
    @_highest
    def recurrence(q, k, v, a, A_log, g_bias, b, at):
        """The delta rule row by row: q, k, v [L, H, D] as convolved, a
        [L, H, D] the gate's projection, b [L, H] beta's -> (o [L, H, D],
        the state [2, H, D, D] after `at[0]` and after `at[1]` rows)."""
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * D ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        g = floor * jax.nn.sigmoid(
            jnp.exp(f32(A_log))[:, None] * (a + f32(g_bias)))
        beta = jax.nn.sigmoid(b)

        def row(carry, xs):
            S, kept = carry
            t, q, k, v, g, beta = xs
            S = jnp.exp(g)[..., None] * S
            x = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
            S = S + k[..., None] * x[:, None, :]
            kept = jnp.where((t + 1 == at)[:, None, None, None], S[None],
                             kept)
            return (S, kept), jnp.einsum("hkv,hk->hv", S, q)

        zero = jnp.zeros((H, D, D), jnp.float32)
        (_, kept), o = jax.lax.scan(
            row, (zero, jnp.stack([zero, zero])),
            (jnp.arange(q.shape[0]), q, k, v, g, beta))
        return o, kept

    @jax.jit
    @_highest
    def gated_norm(o, o_norm, gate):
        """o [L, H, D] normed a head, times sigmoid(gate) [L, H]."""
        return _rms(o, o_norm, eps) * jax.nn.sigmoid(gate)[..., None]

    @jax.jit
    @_highest
    def latent(ckr, kv_norm):
        """[L, R + Dr] as projected -> the normed latent [L, R] and the
        rotated key [L, Dr]."""
        return _rms(ckr[:, :R], kv_norm, eps), _rope(ckr[:, R:], theta)

    @jax.jit
    @_highest
    def heads(q, q_norm, c, k_r, w_ukv):
        """A block of heads: q [L, h, Dn + Dr] as projected, the latent and
        the rotated key, w_ukv [R, h, Dn + Dv] -> q, k [L, h, Dn + Dr] and
        v [L, h, Dv]."""
        if qk_norm:
            q = _rms(q, q_norm, eps)
        q = jnp.concatenate([q[..., :Dn], _rope(q[..., Dn:], theta)], -1)
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
        did not take it. The groups and the experts by SORTING."""
        s = jax.nn.sigmoid(normed @ f32(router))
        choose = s + f32(bias)
        L, E = s.shape
        if n_group > 1:
            by_group = choose.reshape(L, n_group, E // n_group)
            score = jnp.sort(by_group, axis=-1)[..., -2:].sum(-1)
            kept = jnp.argsort(-score, axis=-1)[:, :topk_group]
            stays = (kept[:, :, None] == jnp.arange(n_group)).any(1)
            choose = jnp.where(stays[:, :, None], by_group,
                               -jnp.inf).reshape(L, E)
        idx = jnp.argsort(-choose, axis=-1)[:, :top_k]
        w = jnp.take_along_axis(s, idx, axis=-1)
        if norm_topk:
            w = w / w.sum(-1, keepdims=True)
        g = jnp.einsum("lk,lke->le", w * route_scale, jax.nn.one_hot(
            idx, E, dtype=jnp.float32))
        return g[:, first:first + held]

    @jax.jit
    @_highest
    def add_expert(acc, normed, gate_e, w_gate, w_up, w_down):
        y = (jax.nn.silu(normed @ f32(w_gate)) * (normed @ f32(w_up))) \
            @ f32(w_down)
        return acc + gate_e[:, None] * y

    return (norm, dot, conv_silu, recurrence, gated_norm, latent, heads,
            attend, swiglu, gates, add_expert)


def kda_mixer(x, p, m: dict, at=(0, 0)):
    """One KDA layer's mixer: [L, d] normed input -> ([L, d], the state
    [2, H, D, D] and the convolution's tail [2, 3, 3 H D] after `at[0]`
    and after `at[1]` rows)."""
    import jax.numpy as jnp
    _, dot, conv_silu, recurrence, gated_norm, *_ = _fns(m)
    L, H, D = x.shape[0], m["num_attention_heads"], m["head_dim"]
    taps = m["short_conv_kernel_size"]
    flat = lambda n: dot(x, p[n]["kernel"]).reshape(L, H * D)  # noqa: E731
    qkv = jnp.concatenate([flat("q"), flat("k"), flat("v")], axis=-1)
    tails = jnp.stack([jnp.concatenate(
        [jnp.zeros((taps - 1, 3 * H * D), jnp.float32), qkv[:n]])[-(taps - 1):]
        for n in at])
    q, k, v = (a.reshape(L, H, D) for a in jnp.split(
        conv_silu(qkv, p["conv_w"]), 3, axis=-1))
    o, states = recurrence(
        q, k, v, dot(x, p["g"]["kernel"]).reshape(L, H, D), p["A_log"],
        p["g_bias"], dot(x, p["beta"]["kernel"]), jnp.asarray(at))
    o = gated_norm(o, p["o_norm"], dot(x, p["gate"]["kernel"]))
    return dot(o, p["o"]["kernel"]), states, tails


def latent_mixer(x, p, m: dict):
    """One latent layer's attention branch: [L, d] normed input -> ([L, d],
    what a position keeps [L, 512 + 64]: the normed latent and the rotated
    key)."""
    import jax.numpy as jnp
    _, dot, _, _, _, latent, heads, attend, *_ = _fns(m)
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
    return out, jnp.concatenate([c, k_r], axis=-1)


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


def hidden_states(params, m: dict, tokens, states_after=(0, 0)):
    """Final-norm hidden states [L, d] of one sequence `tokens` [L]; beside
    them each KDA layer's state [2, H, D, D] and convolution's tail
    [2, 3, 3 H D] after `states_after[0]` and `states_after[1]` rows, and
    each latent layer's cached rows [L, 576]."""
    import jax.numpy as jnp
    norm, *_, swiglu, _, _ = _fns(m)
    L = len(tokens)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    states, tails, latents = [], [], []
    for i, kind in enumerate(kinds(m)):
        p = params[f"layer_{i}"]
        u = norm(x, p["attn_norm"]["scale"])
        if kind == "kda":
            y, s, c = kda_mixer(u, p["attn"], m, states_after)
            states.append(s)
            tails.append(c)
        else:
            y, kept = latent_mixer(u, p["attn"], m)
            latents.append(kept)
        x = x + y
        u = norm(x, p["mlp_norm"]["scale"])
        if i < m["first_k_dense_replace"]:
            w = [p["mlp"][n]["kernel"] for n in ("gate", "up", "down")]
            x = x + jnp.concatenate([swiglu(u[at:at + ROW_BLOCK], *w)
                                     for at in range(0, L, ROW_BLOCK)])
        else:
            x = x + expert_layer(u, p["moe"], m)
    return norm(x, params["final_norm"]["scale"]), states, tails, latents


def logits(params, m: dict, tokens, rows=None):
    """[L, vocab] float32 next-token logits of one sequence, or of its
    positions rows = (first, end) alone."""
    _, dot, *_ = _fns(m)
    h = hidden_states(params, m, tokens)[0]
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
                        with_spread=False, with_rows=False, also=()):
    """For each generated token: the largest reference logit at its
    position minus the reference logit of the token the system chose. One
    pass over prompt + generated; `pad_to` pads the sequence at its end
    (every layer is causal: later positions change no earlier one).
    `with_rows`: -> {"gaps", "spread" (the logits'), "rows" (the reference's
    logits [len(generated), vocab] at the scored positions), "also" (its
    logits at the positions `also`), "states" and "tails" (each KDA layer's
    state [2, H, D, D] and tail [2, 3, 3 H D] after the prompt [0] and
    after the last scored position [1]), "latents" (each latent layer's
    cached rows [n, 576] of the sequence's n real positions)}."""
    import jax.numpy as jnp
    import numpy as np
    _, dot, *_ = _fns(m)
    seq = (list(prompt) + list(generated))[:-1]
    n = len(seq)
    seq = seq + [0] * max(0, (pad_to or 0) - n)
    h, states, tails, latents = hidden_states(params, m, seq,
                                              (len(prompt), n))
    rows = dot(h[len(prompt) - 1:n], params["unembed"])
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(generated)[:, None], axis=-1)[:, 0]
    gaps = np.asarray(rows.max(-1) - chosen, np.float64).tolist()
    if not (with_rows or with_spread):
        return gaps
    spread = float(jnp.std(rows, axis=-1).mean())
    if not with_rows:
        return gaps, spread
    return {"gaps": gaps, "spread": spread, "rows": rows,
            "also": dot(h[jnp.asarray(also)], params["unembed"])
            if len(also) else None,
            "states": states, "tails": tails,
            "latents": [kept[:n] for kept in latents]}
