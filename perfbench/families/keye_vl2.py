"""The `keye_vl2` family: the language model of Keye-VL-2.0-30B-A3B, its
published keys mapped to the program's `TransformerLM` (grouped-query
attention with a head size of its own and q/k head norms, a learned
sparse-attention indexer in every layer, models/sparse_attention.py, and
an expert layer that holds one rank's share of the experts, models/moe.py).

What a family states is listed in families/mistral.py; this family's
plain reference is families/keye_vl2_reference.py, and its counts are the
new mathematics': a decode row reads min(live, topk) rows of K and V and
the live rows of indexer keys, and the weights stored are the held
experts'.
"""

from __future__ import annotations

import math
import os
from typing import Iterable

from perfbench.families.keye_vl2_reference import (  # noqa: F401
    batch_loss, experts_first, teacher_forced_gaps)
from perfbench.spec import ROOT, SpecError


# ------------------------------------------------ configuration -> program
_MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "moe_intermediate_size": "d_ff",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "num_experts": "n_experts", "num_experts_per_tok": "expert_top_k",
}


def model_kwargs(cfg: dict) -> dict:
    """The configuration file as keyword arguments of TransformerConfig.
    Refuses what the program cannot state, or states otherwise."""
    def refuse(ok, why):
        if not ok:
            raise SpecError(why)
    refuse(os.path.isfile(os.path.join(
        ROOT, "ray_tpu", "models", "sparse_attention.py")),
        "this checkout's program states no sparse-attention indexer "
        "(ray_tpu/models/sparse_attention.py): it cannot run the family")
    refuse(cfg.get("hidden_act", "silu") == "silu",
           "the program's experts are SwiGLU")
    refuse(not cfg.get("attention_bias"), "the program's projections have "
           "no bias")
    refuse(cfg.get("decoder_sparse_step", 1) == 1
           and not cfg.get("mlp_only_layers"),
           "the program's layers are all of one kind: every one has experts")
    refuse(cfg.get("norm_topk_prob", True), "the program renormalises the "
           "top-k gates")
    refuse(not cfg.get("use_sliding_window") and not cfg.get(
        "sliding_window"), "the program has no sliding window")
    sa = cfg["sa_config"]
    refuse(sa.get("indexer_num_kv_heads", 1) == 1,
           "the program's indexer has one key a position")
    engine = cfg.get("engine") or {}
    refuse(engine.get("max_len", 0) <= cfg["max_position_embeddings"],
           "the engine's slots pass max_position_embeddings")
    refuse(engine.get("prefix_cache_slots", 0) == 0,
           "prefix blocks hold K and V only, not the indexer's keys "
           "(inference/kv_cache.py BlockStore): prefix_cache_slots must be 0")
    first, held = experts_first(cfg), cfg["num_local_experts"]
    refuse(0 < held and first + held <= cfg["num_experts"],
           f"experts {first}..{first + held} are not among the router's "
           f"{cfg['num_experts']}")
    kw = {dst: cfg[src] for src, dst in _MODEL_KEYS.items()}
    kw.update(qk_norm=True, index_heads=sa["indexer_num_heads"],
              index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
              experts_held=[first, held], dtype="bfloat16",
              param_dtype=cfg.get("param_dtype", "bfloat16"))
    kw.update(cfg.get("program") or {})
    return kw


def build_model(kw: dict):
    """In a process that may import JAX: kwargs -> the flax module."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import TransformerConfig
    kw = dict(kw)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    kw["experts_held"] = tuple(kw["experts_held"])
    return TransformerLM(TransformerConfig(**kw))


# ---------------------------------------------------------- seeded weights
# Drawn so that the seeded model is not degenerate at 4k-17k positions, which
# the program's own initialisers (tables 0.02, every matmul 1/sqrt(fan_in),
# norms 1) make it: attention over thousands of random keys is near uniform,
# so every position's output is the same running mean of V, the residual
# (0.02 an element) is swamped by it, hidden states collapse to one
# direction from the second layer on, and every token of a tile routes to
# the same experts (loads of 1024 / 0 read on the chip, PERF.md section 6,
# PR 34); nothing a fault changes then reaches the logits. Here the token's
# own embedding carries the residual (1.0), attention is peaked as a trained
# model's is (the q head norm's scale is drawn N(0, 2^2): attention logits of
# deviation 2, a few dozen positions a head), its output projection is damped
# (0.25: what every position has in common passes attention whole while what
# is its own is averaged down, so a larger factor compounds into the same
# collapse over 16 layers), and the experts' output is doubled, so that the
# indexer's selection, attention and one expert each move the logits by more
# than bf16 rounding does. Routing is then even (the fullest of 16 experts
# takes 79-88 of a tile's 1024 picks, 64 expected).
EMBED_STD, Q_NORM_STD, O_GAIN, DOWN_GAIN = 1.0, 2.0, 0.25, 2.0


def weight_rule(names, shape):
    """A leaf's draw: None for ones (the norms' scales but q_norm's), zeros
    for the indexer LayerNorm's bias, else (standard deviation, whether the
    first axis is the layers' stack); an unknown leaf raises."""
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    if leaf == "scale":
        return (Q_NORM_STD, True) if names[-2] == "q_norm" else None
    if leaf == "bias":
        return 0.0, False
    if leaf in ("embed", "unembed"):
        return (EMBED_STD if leaf == "embed" else 0.02), False
    stacked = shape[1:]                      # without the layers axis
    gain = 1.0
    if leaf in ("q", "k", "v", "router", "index_q", "index_k", "index_w"):
        fan_in = stacked[0]                  # [d_model, ...]
    elif leaf == "o":
        fan_in, gain = stacked[0] * stacked[1], O_GAIN   # [heads, D, d_model]
    elif leaf in ("gate", "up", "down"):
        fan_in = stacked[-2]                 # [experts, in, out]
        gain = DOWN_GAIN if leaf == "down" else 1.0
    else:
        raise KeyError(leaf)
    return gain / math.sqrt(fan_in), True


# ------------------------------------------------------------- the counts
# `m` below is the configuration file's dict (the model's published keys).
def _attn_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    return 2 * d * m["num_attention_heads"] * hd \
        + 2 * d * m["num_key_value_heads"] * hd          # q, o and k, v


def _indexer_params(m: dict) -> int:
    sa = m["sa_config"]
    return m["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params(m: dict, experts: float) -> float:
    """Matmul parameters of one layer with `experts` experts counted."""
    return _attn_params(m) + _indexer_params(m) \
        + m["hidden_size"] * m["num_experts"] + experts * _expert_params(m)


def stored_param_bytes(m: dict, param_bytes: float) -> float:
    """Bytes of the weights as stored on the device: every layer with the
    experts HELD here, the whole router, both tables. Norms' scales are
    below a thousandth and left out."""
    n = m["num_hidden_layers"] * layer_params(m, m["num_local_experts"])
    n += 2 * m["vocab_size"] * m["hidden_size"]
    return n * param_bytes


def index_score_flops(m: dict, batch: int, length: int) -> float:
    """The index scores over the causal half, a layer: one multiply-add a
    (query, key, indexer head, indexer dimension)."""
    sa = m["sa_config"]
    return 2.0 * batch * length * length / 2.0 \
        * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def causal_attention_flops(m: dict, batch: int, length: int,
                           backward: bool) -> float:
    """The one-shot path: QK^T and AV of a query over the positions it
    SELECTED, min(topk, t + 1) of them (summed over t), 2 matmuls x 2
    FLOP x heads x head size each, plus the index scores over the causal
    half; the backward twice the forward."""
    topk = m["sa_config"]["topk"]
    n = min(length, topk)
    selected = n * (n + 1) / 2.0 + (length - n) * topk
    fwd = m["num_hidden_layers"] * (
        4.0 * batch * selected * m["num_attention_heads"] * m["head_dim"]
        + index_score_flops(m, batch, length))
    return fwd * (3.0 if backward else 1.0)


def train_step_flops(m: dict, batch: int, length: int) -> float:
    """Useful forward + backward FLOPs of one training step of this
    share: 6 a matmul parameter a token, with the experts a token uses
    that are held here (num_experts_per_tok x held / all, on average),
    plus attention and the index scores."""
    used = m["num_experts_per_tok"] * m["num_local_experts"] \
        / m["num_experts"]
    n = m["num_hidden_layers"] * layer_params(m, used)
    n += m["hidden_size"] * m["vocab_size"]
    return 6.0 * n * batch * length \
        + causal_attention_flops(m, batch, length, backward=True)


def decode_step_bytes(m: dict, live_lens: Iterable[float],
                      param_bytes: float, kv_bytes: float) -> float:
    """Bytes one decode step must read: the weights as stored (the
    program's dispatch runs every held expert), only the unembedding half
    of the tables, and a layer a slot min(live, topk) rows of K and V and
    the live rows of indexer keys. `live_lens` is a length a slot. (No
    cell reads it yet: `decode_roofline_share` moves a tail this cell does
    not report, and its reader hands the SUM of the lengths as one number,
    which min(live, topk) cannot take: PERF.md section 7.)"""
    sa = m["sa_config"]
    lens = [float(n) for n in live_lens]
    w = stored_param_bytes(m, param_bytes) \
        - m["vocab_size"] * m["hidden_size"] * param_bytes
    kv_row = 2.0 * m["num_key_value_heads"] * m["head_dim"] * kv_bytes
    rows = sum(min(n, sa["topk"]) * kv_row
               + n * sa["indexer_head_dim"] * kv_bytes for n in lens)
    return w + m["num_hidden_layers"] * rows
