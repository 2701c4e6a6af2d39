"""The `sarvam_mla` family: sarvam-105b's published keys mapped to the
program's `TransformerLM` with layers of the kind "mla"
(models/latent_attention.py): latent attention whose cache is ONE row of
kv_lora_rank + qk_rope_head_dim values a position a layer for all heads, a
query projected straight from the hidden (no `q_lora_rank`), YaRN's rotary
on 64 of a head's 192 dimensions; a leading dense layer; an expert layer
routed by sigmoid scores with a selection bias, a shared expert, and one
rank's share of the routed experts (models/moe.py, as the `afmoe` family).

What a family states is listed in families/mistral.py; this family's plain
reference is families/sarvam_mla_reference.py, its controls
families/sarvam_mla_controls.py, and its counts are the new mathematics': a
decode row reads 576 values a position a layer whatever the heads, the
weights stored are the held experts'.

Its comparison with the reference has the `afmoe` family's THREE numbers a
case (`scored`, folded into the harness's one share by that family's
`folded`): each served token's gap below its position's largest reference
logit; the case's `logit_rms`, the program's own logits teacher-forced on
the served tokens through the program's own one-slot `SlotPool`
(`program_rows`: tile by tile into the scratch, expanded; the scratch made
the slot; row by row out of it, absorbed) against the reference's expanded
forward at the same positions, the median over positions; and `route_rel`
(`route_deviation`): the program's first expert layer on the served weights
and seeded probe rows in float32 against the reference's, which is what
tells weighing by score + bias.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Iterable

from perfbench.families import sarvam_mla_reference as reference
from perfbench.families.afmoe import folded
from perfbench.families.falcon_h1 import logit_deviation
from perfbench.families.sarvam_mla_reference import (  # noqa: F401
    batch_loss, experts_first)
from perfbench.spec import ROOT, SpecError

# ------------------------------------------------ configuration -> program
_MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "q_head_dim": "head_dim", "kv_lora_rank": "latent_dim",
    "qk_rope_head_dim": "rope_dim", "v_head_dim": "v_head_dim",
    "intermediate_size": "d_ff", "moe_intermediate_size": "expert_d_ff",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "num_experts": "n_experts", "num_experts_per_tok": "expert_top_k",
    "num_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "n_dense_layers", "use_qk_norm": "qk_norm",
    "routed_scaling_factor": "route_scale",
}
_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
              "beta_slow", "mscale_all_dim")
KEY_BLOCK = 512      # both forms walk scratch and pool in such blocks


def model_kwargs(cfg: dict) -> dict:
    """The configuration file as keyword arguments of TransformerConfig.
    Refuses what the program cannot state, or states otherwise."""
    def refuse(ok, why):
        if not ok:
            raise SpecError(why)
    with open(os.path.join(ROOT, "ray_tpu", "models",
                           "transformer.py")) as f:
        refuse("latent_pool_bytes" in f.read(),
               "this checkout's program states no latent attention "
               "(ray_tpu/models/transformer.py \"mla\", latent_pool_bytes): "
               "it cannot run the family")
    refuse(cfg.get("hidden_act", "silu") == "silu",
           "the program's MLP and experts are SwiGLU")
    refuse(cfg.get("q_lora_rank") is None,
           "the program projects the query straight from the hidden")
    refuse(cfg["q_head_dim"] == cfg["qk_nope_head_dim"]
           + cfg["qk_rope_head_dim"]
           and cfg["head_dim"] == cfg["kv_lora_rank"]
           + cfg["qk_rope_head_dim"]
           and cfg["v_head_dim"] == cfg["qk_nope_head_dim"],
           "q_head_dim is nope + rope, head_dim the cached row (latent + "
           "rope), and kv_up's halves are equal")
    scaling = cfg.get("rope_scaling") or {}
    refuse(scaling.get("type") == "deepseek_yarn"
           and all(k in scaling for k in _YARN_KEYS)
           and scaling.get("mscale") == scaling["mscale_all_dim"],
           "the family's rotary is deepseek_yarn with mscale == "
           "mscale_all_dim (cos and sin carry no factor of their own)")
    refuse(cfg.get("moe_router_enable_expert_bias") is True
           and cfg.get("n_group", 1) == 1,
           "the family's router is a sigmoid over ungrouped experts with a "
           "selection bias")
    first, held = experts_first(cfg), cfg["num_local_experts"]
    refuse(0 < held and first + held <= cfg["num_experts"],
           f"experts {first}..{first + held} are not among the router's "
           f"{cfg['num_experts']}")
    engine = cfg.get("engine") or {}
    refuse(engine.get("max_len", 0) <= cfg["max_position_embeddings"],
           "the engine's slots pass max_position_embeddings")
    block = min(KEY_BLOCK, engine["prefill_budget"])
    refuse((engine["max_len"] + engine["prefill_budget"]) % block == 0
           and engine["max_len"] % block == 0,
           f"a slot, and a slot with the largest tile, hold whole blocks "
           f"of {KEY_BLOCK} keys")
    refuse(engine.get("prefix_cache_slots", 0) == 0,
           "prefix blocks hold K and V, not a latent (inference/kv_cache.py "
           "BlockStore): prefix_cache_slots must be 0")
    kw = {dst: cfg[src] for src, dst in _MODEL_KEYS.items()}
    kw.update(rope_theta=float(cfg["rope_theta"]),
              n_kv_heads=cfg["num_attention_heads"],
              mixer_kinds=["mla"] * cfg["num_hidden_layers"],
              rope_yarn=[scaling[k] for k in _YARN_KEYS],
              router="sigmoid", route_norm=True,
              experts_held=[first, held], scan_layers=False,
              dtype="bfloat16", param_dtype=cfg.get("param_dtype",
                                                    "bfloat16"))
    kw.update(cfg.get("program") or {})
    return kw


def build_model(kw: dict):
    """In a process that may import JAX: kwargs -> the flax module."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import TransformerConfig, Yarn
    kw = dict(kw)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    for key in ("mixer_kinds", "experts_held"):
        kw[key] = tuple(kw[key])
    kw["rope_yarn"] = Yarn(*kw["rope_yarn"])
    return TransformerLM(TransformerConfig(**kw))


# ------------------------------------------------- against the reference
@functools.lru_cache(maxsize=2)
def _programs(model):
    """The model's cached forward as the engine's programs call it: a
    prefill tile into a scratch (the logits of its rows the caller names),
    and one decode row against the pool. (A control that plants a fault in
    a function these trace clears this cache: families/
    sarvam_mla_controls.py.)"""
    import jax

    def forward(chunked, params, toks, cache, rows=None):
        return model.apply({"params": params}, toks, cache=cache,
                           chunked_prefill=chunked, logit_rows=rows)

    return (jax.jit(functools.partial(forward, True)),
            jax.jit(functools.partial(forward, False)))


def program_rows(params, m: dict, prompt, generated, model=None):
    """What the PROGRAM computes for one case, teacher-forced on the served
    tokens through its own one-slot `SlotPool`: the prompt prefilled in
    tiles of the engine's budget into a scratch (the expanded form), the
    scratch made the pool's one slot, then one decode row a served token
    (the absorbed form), each reading and writing the pool where it lies:
    the engine's calls, with the served tokens fed in place of the sampled
    ones. -> its logits [len(generated), vocab], float32, at the positions
    `teacher_forced_gaps` scores, as "rows"; beside them "route_rel"
    (`route_deviation` of the same model)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.inference import kv_cache
    if model is None:
        model = build_model(model_kwargs(m))
    tile, max_len = m["engine"]["prefill_budget"], m["engine"]["max_len"]
    pool = kv_cache.SlotPool(model.cfg, 1, max_len, max_len,
                             max_len + tile, model.cfg.dtype)
    names = tuple(pool.shapes)
    tiled, row = _programs(model)
    seq = np.asarray(list(prompt) + list(generated)[:-1], np.int32)
    n = len(prompt)
    scratch = pool.new_scratch()
    for at in range(0, n, tile):
        real = min(tile, n - at)
        toks = np.zeros((1, tile), np.int32)
        toks[0, :real] = seq[at:at + real]
        lg, new = tiled(params, jnp.asarray(toks), dict(
            zip(names, scratch), idx=jnp.int32(at),
            real=(jnp.arange(tile) < real)[None]),
            jnp.asarray([real - 1], jnp.int32))
        scratch = tuple(new[k] for k in names)
    rows = [lg[0, 0]]
    pool.insert(scratch, 0)
    del scratch, new
    for at in range(n, len(seq)):
        lg, new = row(params, jnp.asarray(seq[at:at + 1])[None], dict(
            zip(names, pool.pools()), idx=jnp.asarray([at], jnp.int32)))
        pool.rebind(tuple(new[k] for k in names))
        rows.append(lg[0, 0])
    return {"rows": jnp.stack(rows).astype(jnp.float32),
            "route_rel": route_deviation(params, m, model)}


PROBE_ROWS = 256


def route_deviation(params, m: dict, model) -> float:
    """The program's FIRST expert layer (the module `model` is built of,
    with the served weights, its arithmetic in float32 at "highest")
    against the reference's `expert_layer`, on PROBE_ROWS seeded rows of
    unit normals: the NINTH DECILE over the rows of the distance as a share
    of the reference's norm (families/afmoe.py `route_deviation` says why
    that decile)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.moe import MoEMLP
    p = params[f"layer_{m['first_k_dense_replace']}"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(50),
                          (1, PROBE_ROWS, m["hidden_size"]), jnp.float32)
    layer = MoEMLP(dataclasses.replace(model.cfg, dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(lambda p, x: layer.apply(
            {"params": p}, x, exact=True))(p, x)
    want = reference.expert_layer(x[0], p, m)
    each = jnp.linalg.norm(got[0] - want, axis=-1) \
        / jnp.linalg.norm(want, axis=-1)
    return float(np.percentile(np.asarray(each, np.float64), 90.0))


def scored(params, m: dict, prompt, generated, pad_to=None, program=None):
    """One case's numbers: `gaps` (each served token's gap below its
    position's largest reference logit), `spread` (the reference logits'),
    `logit_rms` and `logit_rms_each` (`logit_deviation` at the scored
    positions), `route_rel` (`route_deviation`). `program`: what
    `program_rows` gave; computed here from `params` where not given."""
    got = program if program is not None \
        else program_rows(params, m, prompt, generated)
    ref = reference.teacher_forced_gaps(params, m, prompt, generated,
                                        pad_to=pad_to, with_rows=True)
    dev, each = logit_deviation(got["rows"], ref["rows"])
    return {"gaps": ref["gaps"], "spread": ref["spread"], "logit_rms": dev,
            "logit_rms_each": each, "route_rel": got["route_rel"]}


def teacher_forced_gaps(params, m: dict, prompt, generated, pad_to=None,
                        with_spread=False, program=None):
    """What the harness asks of a family (replica.bench_reference): a gap
    a served token, `scored` and `folded`."""
    score = scored(params, m, prompt, generated, pad_to, program)
    gaps = folded(score, m["reference_tolerance"])
    return (gaps, score["spread"]) if with_spread else gaps


# ---------------------------------------------------------- seeded weights
# Drawn so that the seeded model is not degenerate at 6k-10k positions, as
# the `afmoe` family's are and for its reasons (families/afmoe.py, PERF.md
# section 6, PR 34 and PR 46), in a block that has no norm at a branch's
# output:
# - the q head norm's scale is drawn N(0, Q_NORM_STD^2): a head's score is
#   192^-1/2 m^2 (m^2 = 1.874) times a sum over 192 products of a unit key
#   (the latent's up-projection, 128, and the rotated key, 64: a third of
#   the score's variance is the rope key's), so attention logits have
#   deviation 1.874 x 1.6 = 3 and a head attends one to three positions
#   anywhere in its context: nothing is averaged down, and the part of the
#   hidden states that all positions share does not grow;
# - the latent's norm's scale is drawn N(0, KV_NORM_STD^2), not ones: the
#   down-projection of a normed input is near unit already, so a program
#   that skipped a norm of ones would read as sound; a latent element keeps
#   unit variance, so K and V do;
# - the attention branch joins the residual at O_GAIN an element beside the
#   token's own embedding (EMBED), the dense MLP and the shared expert at
#   0.6; a routed expert's output is doubled (DOWN_GAIN): of a token's
#   eight picks one falls on a held expert on average, weighed 2.5 / 8, so
#   that one expert moves the logits by more than bf16's rounding does;
# - the router's scores are sigmoids of unit normals, the selection bias
#   N(0, BIAS_STD^2): not zero, so that choosing and weighing are told
#   apart (`route_rel`), and no larger (a drawn bias unbalances the loads:
#   families/afmoe.py);
# - logits of deviation LOGITS.
EMBED, Q_NORM_STD, KV_NORM_STD, O_GAIN, DOWN_GAIN, BIAS_STD, LOGITS = (
    1.0, 1.6, 1.0, 0.5, 2.0, 0.005, 1.2)


def weight_rule(names, shape):
    """A leaf's draw: None for ones (the norms' scales but q_norm's and
    kv_norm's), else (standard deviation, False: no leaf of this tree is a
    stack); an unknown leaf raises."""
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    if leaf == "scale":
        return {"q_norm": (Q_NORM_STD, False),
                "kv_norm": (KV_NORM_STD, False)}.get(names[-2])
    if leaf == "embed":                     # [vocab, d_model]: unit rows
        return EMBED, False
    if leaf == "unembed":                   # [d_model, vocab]
        return LOGITS / math.sqrt(shape[0]), False
    if leaf == "router_bias":
        return BIAS_STD, False
    if names[-1] == "kernel":               # a projection: [fan_in, ...]
        if leaf not in ("q", "kv_down", "o", "gate", "up", "down",
                        "shared_gate", "shared_up", "shared_down"):
            raise KeyError(leaf)
        if leaf == "o":                     # [heads, v_head_dim, d_model]
            return O_GAIN / math.sqrt(shape[0] * shape[1]), False
        return 1.0 / math.sqrt(shape[0]), False
    if leaf in ("router", "kv_up"):         # [d_model, experts], [R, H, ..]
        return 1.0 / math.sqrt(shape[0]), False
    if leaf in ("gate", "up", "down"):      # the experts': [held, in, out]
        return (DOWN_GAIN if leaf == "down" else 1.0) \
            / math.sqrt(shape[1]), False
    raise KeyError(leaf)


# ------------------------------------------------------------- the counts
# `m` below is the configuration file's dict (the model's published keys).
def latent_row_values(m: dict) -> int:
    """What a position keeps a layer: the latent and the one rotated key."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def _up_width(m: dict) -> int:
    """kv_up's outputs a latent: each head's K (without the rope key) and V."""
    return m["num_attention_heads"] * (m["qk_nope_head_dim"]
                                       + m["v_head_dim"])


def _attn_params(m: dict) -> int:
    d, H = m["hidden_size"], m["num_attention_heads"]
    return d * H * m["q_head_dim"] + d * latent_row_values(m) \
        + m["kv_lora_rank"] * _up_width(m) + H * m["v_head_dim"] * d


def _swiglu_params(m: dict, width: int) -> int:
    return 3 * m["hidden_size"] * width


def layer_params(m: dict, i: int, experts: float) -> float:
    """Matmul parameters of layer i with `experts` routed experts counted."""
    if i < m["first_k_dense_replace"]:
        return _attn_params(m) + _swiglu_params(m, m["intermediate_size"])
    wide = m["moe_intermediate_size"]
    return _attn_params(m) + m["hidden_size"] * m["num_experts"] \
        + _swiglu_params(m, wide * m["num_shared_experts"]) \
        + experts * _swiglu_params(m, wide)


def stored_param_bytes(m: dict, param_bytes: float) -> float:
    """Bytes of the weights as stored on the device: every layer with the
    experts HELD here, the whole router and shared expert, both tables'
    slice. Norms' scales and the bias are below a thousandth, left out."""
    n = sum(layer_params(m, i, m["num_local_experts"])
            for i in range(m["num_hidden_layers"]))
    n += 2 * m["vocab_size"] * m["hidden_size"]
    return n * param_bytes


def causal_pairs(pos0: float, rows: float) -> float:
    """(query, key) pairs of `rows` rows at positions pos0 .. in one layer:
    row t attends t + 1 keys."""
    return rows * (pos0 + (rows + 1) / 2.0)


def mla_attend_flops(m: dict, pairs: float, rows: float) -> float:
    """The tiles' attention over `pairs` (query, key) pairs a layer, by
    `rows` rows: q.k at q_head_dim and p.v at v_head_dim a head a pair, and
    the up-projection of the rows' OWN latents to K and V (what the
    expanded form cannot do without; the up-projection of the cached
    positions again for every tile is the form's own cost, not the
    work's). The same whatever form implements it."""
    H = m["num_attention_heads"]
    return m["num_hidden_layers"] * (
        pairs * H * 2.0 * (m["q_head_dim"] + m["v_head_dim"])
        + rows * 2.0 * m["kv_lora_rank"] * _up_width(m))


def mla_attend_bytes(m: dict, keys: float, act_bytes: float) -> float:
    """The least the tiles' attention reads: the latents of the `keys`
    positions a tile attends, once a layer."""
    return m["num_hidden_layers"] * keys * latent_row_values(m) * act_bytes


def mla_row_bytes(m: dict, live: float, kv_bytes: float) -> float:
    """The least the decode rows read: `live` positions of latents (the
    sum over a step's live slots of their lengths) a layer."""
    return m["num_hidden_layers"] * live * latent_row_values(m) * kv_bytes


def mla_row_flops(m: dict, live: float) -> float:
    """The absorbed row's products over `live` positions a layer: every
    head's score at latent + rope and its sum at latent."""
    return m["num_hidden_layers"] * live * m["num_attention_heads"] * 2.0 \
        * (latent_row_values(m) + m["kv_lora_rank"])


def causal_attention_flops(m: dict, batch: int, length: int,
                           backward: bool) -> float:
    """The one-shot path over a sequence, expanded; the backward twice the
    forward."""
    fwd = batch * mla_attend_flops(m, causal_pairs(0, length), length)
    return fwd * (3.0 if backward else 1.0)


def train_step_flops(m: dict, batch: int, length: int) -> float:
    """Useful forward + backward FLOPs of one training step of this share:
    6 a matmul parameter a token (kv_up counted with attention, not here),
    with the routed experts a token uses that are held here, plus
    attention. (No cell trains this model.)"""
    used = m["num_experts_per_tok"] * m["num_local_experts"] \
        / m["num_experts"]
    n = sum(layer_params(m, i, used) - m["kv_lora_rank"] * _up_width(m)
            for i in range(m["num_hidden_layers"]))
    n += m["hidden_size"] * m["vocab_size"]
    return 6.0 * n * batch * length \
        + causal_attention_flops(m, batch, length, backward=True)


def decode_step_bytes(m: dict, live_lens: Iterable[float],
                      param_bytes: float, kv_bytes: float) -> float:
    """The LEAST one decode step must move: the weights as stored (the
    program's dispatch runs every held expert; only the unembedding half of
    the tables), and every position of a live slot's latents. `live_lens`
    is a length a live slot (the reader metrics/decode_roofline_share.tok.py
    hands each request's)."""
    w = stored_param_bytes(m, param_bytes) \
        - m["vocab_size"] * m["hidden_size"] * param_bytes
    return w + mla_row_bytes(m, sum(float(n) for n in live_lens), kv_bytes)
