"""The `mistral` family: Mistral's and Mixtral's published keys, mapped to
the program's `TransformerLM` (models/transformer.py, models/moe.py).

A configuration file names its family (`"family": "mistral"`) and the
harness finds `<path>/families/mistral.py` as it finds a reader. What a
family states: the file's keys as the program's arguments, with its
refusals (`model_kwargs`, JSON-able, made where JAX is not imported); the
flax module (`build_model`); the rule for a seeded leaf (`weight_rule`);
the plain reference (`teacher_forced_gaps`, `batch_loss`: this family's is
perfbench/reference.py); and the counts the roofline readers divide by.
"""

from __future__ import annotations

import math
from typing import Iterable

from perfbench.reference import batch_loss, teacher_forced_gaps  # noqa: F401
from perfbench.spec import SpecError

# ------------------------------------------------ configuration -> program
# published key -> TransformerConfig field (models/transformer.py)
_MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "n_experts", "num_experts_per_tok": "expert_top_k",
}


def model_kwargs(cfg: dict) -> dict:
    """The configuration file as keyword arguments of TransformerConfig
    (dtypes as strings; the process that owns JAX turns them into dtypes).
    Refuses what the program cannot state: another activation, a head size
    that is not hidden/heads, a sliding window shorter than the engine's
    slots (the program has no window, so it must be inert)."""
    if cfg.get("hidden_act", "silu") != "silu":
        raise SpecError(f"hidden_act {cfg['hidden_act']!r}: the program's "
                        f"MLP is SwiGLU")
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    if cfg.get("head_dim", hd) != hd:
        raise SpecError("head_dim is not hidden_size / num_attention_heads")
    kw = {dst: cfg[src] for src, dst in _MODEL_KEYS.items() if src in cfg}
    longest = max((cfg.get("engine") or {}).get("max_len", 0),
                  (cfg.get("train") or {}).get("seq_len", 0))
    window = cfg.get("sliding_window")
    if window is not None and longest > window:
        raise SpecError(f"sequences of {longest} pass the sliding window "
                        f"{window}, which the program does not implement")
    if longest > cfg["max_position_embeddings"]:
        raise SpecError(f"sequences of {longest} pass "
                        f"max_position_embeddings")
    kw["dtype"] = "bfloat16"
    kw["param_dtype"] = cfg.get("param_dtype", cfg.get("torch_dtype",
                                                       "bfloat16"))
    for key in ("capacity_factor", "remat_policy", "attention_impl"):
        if key in (cfg.get("program") or {}):
            kw[key] = cfg["program"][key]
    return kw


def build_model(kw: dict):
    """In a process that may import JAX: kwargs -> the flax module."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import TransformerConfig
    kw = dict(kw)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    return TransformerLM(TransformerConfig(**kw))


# ---------------------------------------------------------- seeded weights
def _fan_in(names, shape) -> int:
    """Fan-in of a matmul leaf by its name in the program's tree; an
    unknown name raises, so a change of the tree is noticed here."""
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    stacked = shape[1:]                      # without the layers axis
    if leaf in ("q", "k", "v", "router"):
        return stacked[0]                    # [d_model, ...]
    if leaf == "o":
        return stacked[0] * stacked[1]       # [heads, head_dim, d_model]
    if leaf in ("gate", "up", "down"):
        return stacked[-2]                   # [(experts,) in, out]
    raise KeyError(leaf)


def weight_rule(names, shape):
    """A leaf's draw, following the program's initialisers: None for ones
    (the norms), else (standard deviation, whether the first axis is the
    layers' stack, drawn a slice at a time): 0.02 for the tables,
    1/sqrt(fan_in) for the matmuls."""
    if names[-1] == "scale":
        return None
    if names[-1] in ("embed", "unembed"):
        return 0.02, False
    return 1.0 / math.sqrt(_fan_in(names, shape)), True


# ------------------------------------------------------------- the counts
# `m` below is the configuration file's dict (the model's published keys).
def _attn_params(m: dict) -> int:
    d, h, kv = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hd = d // h
    return d * h * hd * 2 + d * kv * hd * 2          # q, o and k, v


def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]   # gate, up, down


def n_experts(m: dict) -> int:
    return int(m.get("num_local_experts", 0) or 0)


def layer_params(m: dict, active_only: bool) -> int:
    """Matmul parameters of one layer. With experts: all of them (what is
    stored and streamed) or the `num_experts_per_tok` a token uses (what
    does useful work), plus the router."""
    e = n_experts(m)
    if e == 0:
        return _attn_params(m) + _expert_params(m)
    k = m["num_experts_per_tok"] if active_only else e
    return _attn_params(m) + k * _expert_params(m) + m["hidden_size"] * e


def stored_param_bytes(m: dict, param_bytes: float) -> float:
    """Bytes of the weights as stored on the device: every layer with all
    its experts, the embedding and the unembedding (untied). The norms'
    scales and the router (fp32) are below a thousandth and left out."""
    n = m["num_hidden_layers"] * layer_params(m, active_only=False)
    n += 2 * m["vocab_size"] * m["hidden_size"]
    return n * param_bytes


def causal_attention_flops(m: dict, batch: int, length: int,
                           backward: bool) -> float:
    """QK^T and AV over the causal half: 2 matmuls x 2 FLOP x B x L^2/2 x
    (heads x head_dim) a layer forward; the backward is twice the forward
    (recomputation inside the flash backward kernel does not count)."""
    d_attn = m["hidden_size"]           # heads x head_dim
    fwd = m["num_hidden_layers"] * 4.0 * batch * length * length \
        * d_attn / 2.0
    return fwd * (3.0 if backward else 1.0)


def train_step_flops(m: dict, batch: int, length: int) -> float:
    """Useful forward + backward FLOPs of one training step: 6 per matmul
    parameter a token (2 forward, 4 backward) plus causal attention.
    Origin: reports/mfu_ablate.py:train_step_flops (deleted with reports/
    in PR 29), extended with the MoE case: only the experts a token is
    routed to do useful work.
    Recomputed (remat) operations do not count; the embedding lookup is
    not a matmul and does not count; the unembedding does."""
    n = m["num_hidden_layers"] * layer_params(m, active_only=True)
    n += m["hidden_size"] * m["vocab_size"]
    return 6.0 * n * batch * length \
        + causal_attention_flops(m, batch, length, backward=True)


def decode_step_bytes(m: dict, live_lens: Iterable[float],
                      param_bytes: float, kv_bytes: float) -> float:
    """Bytes one decode step must read: the weights as stored (every
    expert: at 16 rows x top-2 over 8 experts nearly all are touched, and
    the program's dense dispatch reads all regardless) and every live
    slot's K and V. The embedding table is read by rows, so only the
    unembedding half of the two tables counts.
    Origin: util/profiling.py:decode_step_bytes."""
    hd = m["hidden_size"] // m["num_attention_heads"]
    w = stored_param_bytes(m, param_bytes) \
        - m["vocab_size"] * m["hidden_size"] * param_bytes
    kv = sum(2.0 * m["num_hidden_layers"] * float(n)
             * m["num_key_value_heads"] * hd * kv_bytes for n in live_lens)
    return w + kv
