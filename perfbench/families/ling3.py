"""The `ling3` family: the language model of Ling-3.0-flash-VL's published
keys mapped to the program's `TransformerLM` with two kinds of layer
(models/transformer.py): "kda" (models/kda.py: delta-rule linear attention
with a decay a channel, a float32 state a head and a convolution's tail) in
five layers of six and "mla" (models/latent_attention.py: latent attention,
one row of kv_lora_rank + qk_rope_head_dim values a position) in the sixth;
a leading dense layer; an expert layer routed by sigmoid scores IN GROUPS
with a selection bias, a shared expert, and one rank's share of the routed
experts, which is exactly one routing group (models/moe.py).

What a family states is listed in families/mistral.py; this family's plain
reference is families/ling3_reference.py, its controls
families/ling3_controls.py. Its comparison with the reference has the
`falcon_h1` family's numbers a case and the `sarvam_mla` family's third
(`scored`, folded into the harness's one share by `folded`): each served
token's gap below its position's largest reference logit; `logit_rms`, the
program's own logits, teacher-forced on the served tokens through the
program's own one-slot `SlotPool` (`program_rows`: tiles, then rows),
against the reference's; `edge_rms`, the same at the three rows that open
every prefill tile after the first (whose convolutions read the tile
before's tails and whose scans start from its state); `state_rel` and
`tail_rel`, the FIRST "kda" layer's state and tail in that pool against the
reference's, right after `insert` and after the last scored token, and
`state_rel_last` and `tail_rel_last`, the LAST one's, each under a limit of
its own; `latent_rel`, what the first latent layer's pool holds of the slot
against the reference's latents and rotated keys; and `route_rel`, the
program's first expert layer in float32 on the served weights against the
reference's.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Iterable

from perfbench.families import ling3_reference as reference
from perfbench.families.falcon_h1 import (logit_deviation, state_deviation,
                                          tail_deviation)
from perfbench.families.ling3_reference import (  # noqa: F401
    batch_loss, experts_first, kinds)
from perfbench.families.sarvam_mla import causal_pairs  # noqa: F401
from perfbench.spec import ROOT, SpecError

# ------------------------------------------------ configuration -> program
_MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "head_dim": "kda_head_dim", "kv_lora_rank": "latent_dim",
    "qk_rope_head_dim": "rope_dim", "v_head_dim": "v_head_dim",
    "intermediate_size": "d_ff", "moe_intermediate_size": "expert_d_ff",
    "max_position_embeddings": "max_seq_len", "rms_norm_eps": "norm_eps",
    "num_experts": "n_experts", "num_experts_per_tok": "expert_top_k",
    "first_k_dense_replace": "n_dense_layers", "use_qk_norm": "qk_norm",
    "routed_scaling_factor": "route_scale", "n_group": "n_group",
    "topk_group": "topk_group", "norm_topk_prob": "route_norm",
    "short_conv_kernel_size": "kda_conv",
}
KEY_BLOCK = 512      # the latent layers walk scratch and pool in such blocks


def model_kwargs(cfg: dict) -> dict:
    """The configuration file as keyword arguments of TransformerConfig.
    Refuses what the program cannot state, or states otherwise."""
    def refuse(ok, why):
        if not ok:
            raise SpecError(why)
    refuse(os.path.isfile(os.path.join(ROOT, "ray_tpu", "models", "kda.py")),
           "this checkout's program states no delta-rule linear attention "
           "(ray_tpu/models/kda.py, the kind \"kda\"): it cannot run the "
           "family")
    refuse(cfg.get("hidden_act", "silu") == "silu" and cfg["linear_silu"],
           "the program's MLP and experts are SwiGLU, a KDA layer's "
           "convolutions end in SiLU")
    refuse(cfg.get("q_lora_rank") is None and not cfg["use_mla_nope"],
           "the program projects a latent layer's query straight from the "
           "hidden and rotates its last qk_rope_head_dim")
    refuse(cfg["v_head_dim"] == cfg["qk_nope_head_dim"]
           and cfg["rotary_dim"] == cfg["qk_rope_head_dim"]
           and cfg["partial_rotary_factor"] * cfg["head_dim"]
           == cfg["rotary_dim"],
           "kv_up's halves are equal; rotary_dim and partial_rotary_factor "
           "are read as the latent layers' qk_rope_head_dim")
    refuse(cfg["no_kda_lora"] and not cfg["use_kda_lora"]
           and cfg["kda_safe_gate"] and cfg["kda_lower_bound"] < 0,
           "the program's KDA gate is a full-rank projection under the "
           "lower-bound (safe) form")
    refuse(cfg["num_kv_heads_for_linear_attn"] in (
           0, cfg["num_attention_heads"]) and cfg["group_norm_size"] == 1
           and cfg["gated_attention_proj_granularity_type"] == "head_wise",
           "a KDA layer has as many key and value heads as query heads, its "
           "output normed a head and gated a head")
    refuse(not (cfg["use_nGPT"] or cfg["scale_router_input"]
                or cfg["value_norm"] or cfg["up_proj_norm"]),
           "the program has no nGPT norms, no scaled router input, no value "
           "norm and no up-projection norm")
    refuse(not any(cfg["expert_swiglu_limit_list"])
           and not any(cfg["share_expert_swiglu_limit_list"])
           and len(cfg["expert_swiglu_limit_list"])
           == len(cfg["share_expert_swiglu_limit_list"])
           == cfg["num_hidden_layers"],
           "the program has no clamped SwiGLU: every layer held has limit 0")
    refuse(cfg.get("moe_router_enable_expert_bias") is True
           and cfg["score_function"] == "sigmoid",
           "the family's router is a sigmoid with a selection bias")
    refuse(cfg["moe_shared_expert_intermediate_size"]
           % cfg["moe_intermediate_size"] == 0,
           "the shared expert is whole experts wide")
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
           "prefix blocks hold K and V, not a latent, a state or a tail "
           "(inference/kv_cache.py BlockStore): prefix_cache_slots must be 0")
    kw = {dst: cfg[src] for src, dst in _MODEL_KEYS.items()}
    kw.update(rope_theta=float(cfg["rope_theta"]),
              kda_gate_floor=float(cfg["kda_lower_bound"]),
              n_kv_heads=cfg["num_attention_heads"],
              # a latent layer's query: nope ‖ rope
              head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
              n_shared_experts=cfg["moe_shared_expert_intermediate_size"]
              // cfg["moe_intermediate_size"],
              mixer_kinds=kinds(cfg), tie_embeddings=False,
              router="sigmoid", experts_held=[first, held],
              scan_layers=False, dtype="bfloat16",
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
    for key in ("mixer_kinds", "experts_held"):
        kw[key] = tuple(kw[key])
    return TransformerLM(TransformerConfig(**kw))


# ------------------------------------------------- against the reference
@functools.lru_cache(maxsize=2)
def _programs(model):
    """The model's cached forward as the engine's programs call it: a
    prefill tile into a scratch (the logits of its rows the caller names),
    and one decode row against the pools. (A control that plants a fault in
    a function these trace clears this cache: families/ling3_controls.py.)"""
    import jax

    def forward(chunked, params, toks, cache, rows=None):
        return model.apply({"params": params}, toks, cache=cache,
                           chunked_prefill=chunked, logit_rows=rows)

    return (jax.jit(functools.partial(forward, True)),
            jax.jit(functools.partial(forward, False)))


EDGE = 3             # rows at a tile's start that read the tile before's tail


def program_rows(params, m: dict, prompt, generated, model=None):
    """What the PROGRAM computes for one case, teacher-forced on the served
    tokens through its own one-slot `SlotPool`: the prompt prefilled in
    tiles of the engine's budget into a scratch (the tiles hand states and
    tails on and write their latents; the last tile's tail is rows no
    request owns), the scratch made the pool's one slot, then one decode
    row a served token. -> {"rows": its logits [len(generated), vocab],
    float32, at the scored positions; "edge", "edge_rows": the positions of
    the first `EDGE` rows of every tile after the first and its logits
    there; "states" [2, layers, H, D, D] and "tails" [2, layers, 3, 3 H D]:
    the pool's, right after `insert` and after the last scored token;
    "latents" [positions, 576]: what the FIRST latent layer's pool holds of
    the slot at the end, the tiles' rows and the decode rows' behind them;
    "route_rel" (`route_deviation` of the same model)}."""
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
    edge, edge_rows = [], []
    for at in range(0, n, tile):
        real = min(tile, n - at)
        toks = np.zeros((1, tile), np.int32)
        toks[0, :real] = seq[at:at + real]
        named = np.asarray(list(range(EDGE)) + [real - 1], np.int32)
        lg, new = tiled(params, jnp.asarray(toks), dict(
            zip(names, scratch), idx=jnp.int32(at),
            real=(jnp.arange(tile) < real)[None]), jnp.asarray(named))
        scratch = tuple(new[k] for k in names)
        if at:
            edge += range(at, at + min(EDGE, real))
            edge_rows.append(lg[0, :min(EDGE, real)])
    rows = [lg[0, EDGE]]
    pool.insert(scratch, 0)
    del scratch, new
    held = [(pool.s[:, 0], pool.c[:, 0])]
    for at in range(n, len(seq)):
        lg, new = row(params, jnp.asarray(seq[at:at + 1])[None], dict(
            zip(names, pool.pools()), idx=jnp.asarray([at], jnp.int32)))
        pool.rebind(tuple(new[k] for k in names))
        rows.append(lg[0, 0])
    held.append((pool.s[:, 0], pool.c[:, 0]))
    return {"rows": jnp.stack(rows).astype(jnp.float32), "edge": edge,
            "edge_rows": jnp.concatenate(edge_rows).astype(jnp.float32)
            if edge else None,
            "states": jnp.stack([s for s, _ in held]),
            "tails": jnp.stack([c for _, c in held]),
            "latents": pool.lat[0, 0, :, :len(seq)].astype(jnp.float32).T,
            "route_rel": route_deviation(params, m, model)}


PROBE_ROWS = 256


def route_deviation(params, m: dict, model) -> float:
    """The program's FIRST expert layer (the module `model` is built of,
    with the served weights, its arithmetic in float32 at "highest")
    against the reference's `expert_layer`, on PROBE_ROWS seeded rows of
    unit normals: the NINTH DECILE over the rows of the distance as a share
    of the reference's norm (families/afmoe.py `route_deviation` says why
    that decile: here more than half the rows take no held expert at all
    and read the shared expert alone on both sides)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.moe import MoEMLP
    p = params[f"layer_{m['first_k_dense_replace']}"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(58),
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
    """One case's numbers: `gaps`, `spread`, `logit_rms` and
    `logit_rms_each`, `edge_rms` (0.0 for a prompt of one tile), `state_rel`
    and `tail_rel` (the FIRST "kda" layer's, which reads the embedding
    alone) and `state_rel_last` and `tail_rel_last` (the LAST one's, behind
    every other layer's bf16 activations), each the larger of right after
    `insert` and after the last scored token, the state's the largest over
    the heads; `latent_rel`, the distance of the first latent layer's cached
    rows (the normed latent and the rotated key of every position, as the
    tiles and then the decode rows wrote them) from the reference's, as a
    share of the reference's norm; `route_rel`."""
    import jax.numpy as jnp
    got = program or program_rows(params, m, prompt, generated)
    ref = reference.teacher_forced_gaps(params, m, prompt, generated,
                                        pad_to=pad_to, with_rows=True,
                                        also=got["edge"])
    dev, each = logit_deviation(got["rows"], ref["rows"])
    edge = logit_deviation(got["edge_rows"], ref["also"])[0] \
        if got["edge"] else 0.0
    by_layer = [float(state_deviation(got["states"][:, j],
                                      ref["states"][j]).max())
                for j in (0, -1)]
    tails = [float(max(tail_deviation(got["tails"][:, j], ref["tails"][j])))
             for j in (0, -1)]
    want = ref["latents"][0]
    lat = float(jnp.sqrt(jnp.sum(jnp.square(got["latents"] - want))
                         / jnp.sum(jnp.square(want))))
    return {"gaps": ref["gaps"], "spread": ref["spread"], "logit_rms": dev,
            "logit_rms_each": each, "edge_rms": edge,
            "state_rel": by_layer[0], "state_rel_last": by_layer[1],
            "tail_rel": tails[0], "tail_rel_last": tails[1],
            "latent_rel": lat, "route_rel": got["route_rel"]}


NUMBERS = ("state_rel", "state_rel_last", "tail_rel", "tail_rel_last",
           "latent_rel", "route_rel")


def folded(score: dict, tol: dict):
    """The case's numbers as the harness's one: where its logit deviation
    (the median over the scored positions, or at the rows that open a tile)
    passes `logit_rms`, or a state's, a tail's, the latents' or the expert
    layer's deviation its limit (`NUMBERS`), every token of the case counts
    as beyond the gap, at `logit_gap` x reading / limit. A number that is
    not finite is over every limit (a program whose state ran away reads
    NaN, and NaN compares as nothing: k left unnormalised did so, PR 58)."""
    over = [score["logit_rms"] / tol["logit_rms"],
            score["edge_rms"] / tol["logit_rms"],
            *(score[k] / tol[k] for k in NUMBERS)]
    if all(math.isfinite(x) for x in over):
        if max(over) <= 1.0:
            return score["gaps"]
        worst = max(over)
    else:
        worst = 1e6
    return [max(g, tol["logit_gap"] * worst) if math.isfinite(g)
            else tol["logit_gap"] * worst for g in score["gaps"]]


def teacher_forced_gaps(params, m: dict, prompt, generated, pad_to=None,
                        with_spread=False, program=None):
    """What the harness asks of a family (replica.bench_reference): a gap
    a served token, `scored` and `folded`."""
    score = scored(params, m, prompt, generated, pad_to, program)
    gaps = folded(score, m["reference_tolerance"])
    return (gaps, score["spread"]) if with_spread else gaps


# ---------------------------------------------------------- seeded weights
# Drawn so that every mechanism reaches the logits and none saturates, in a
# block that has no norm at a branch's output (as the `sarvam_mla` family's,
# families/sarvam_mla.py, for the latent layers, the router and the
# experts):
# - a KDA layer's q, k, v, g projections at unit gain and the taps at
#   1 / sqrt(4): a convolved channel is a unit normal before its SiLU;
# - the gate: g = -5 sigmoid(exp(A) (a + b)) with a a unit normal, A ~
#   N(0, A_LOG_STD^2) a head and b ~ N(0, G_BIAS_STD^2) a channel (the
#   harness draws a deviation and no mean, perfbench/weights.py, so the
#   spread is made by the deviation alone): exp(A) (a + b) is about
#   N(0, 5.1^2), and a layer's 4,096 channels go from forgetting within a
#   token (the half above 0: g below -2.5) over a memory of 4 tokens (28%
#   of them below -3: g = -0.24), 30 tokens (16% below -5) and 600 (6% below
#   -8) to 4,400 (2.5% below -10), and a token moves its own decay by
#   e^(+-1);
# - beta's projection at BETA_GAIN: logits of deviation 1.5, beta from 0.18
#   to 0.82 at one deviation; the head gate's at 1;
# - the output is normed a head, so `o` sees unit rows times a gate of a
#   half: O_GAIN an element as the latent layers' branch;
# - a latent layer's q norm's scale N(0, Q_NORM_STD^2): attention logits of
#   deviation 2 (no YaRN factor here), a head attends a few positions
#   anywhere in its context; the latent's norm's scale N(0, 1), not ones;
# - the dense MLP and the shared expert at 0.6; a routed expert's output at
#   DOWN_GAIN = 0.25, NOT the other share-holding families' 2: the twelve
#   expert layers' top-k is where bf16's rounding turns into whole steps (a
#   row whose 4th and 5th group, or 8th and 9th expert, stand a rounding
#   apart takes other experts than the float32 reference's row, and under
#   routing in groups a flipped GROUP moves two of its picks at once): at 2
#   the sound program's logits stood 0.24 from the reference's and the last
#   KDA layer's state 41%, each expert layer adding some 5% (read by layer);
#   at 1 0.096 and 17%, at 0.5 0.028-0.037 and 10%, at 0.25 0.023 and 4.4%
#   (my chip runs, PR 58: PERF.md section 6). What the router does is held
#   to the reference by `route_rel`, in float32, whatever the experts weigh;
# - the router's scores are sigmoids of unit normals (no group collapses: a
#   group's score is the sum of two of 64 such), the selection bias
#   N(0, BIAS_STD^2), not zero, so that choosing and weighing are told apart;
# - logits of deviation LOGITS.
EMBED, Q_NORM_STD, KV_NORM_STD, O_GAIN, DOWN_GAIN, BIAS_STD, LOGITS = (
    1.0, 2.0, 1.0, 0.5, 0.25, 0.005, 1.2)
A_LOG_STD, G_BIAS_STD, BETA_GAIN = 0.3, 5.0, 1.5


def weight_rule(names, shape):
    """A leaf's draw: None for ones (the norms' scales but q_norm's and
    kv_norm's), else (standard deviation, False: no leaf of this tree is a
    stack); an unknown leaf raises."""
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    if leaf == "scale":
        return {"q_norm": (Q_NORM_STD, False),
                "kv_norm": (KV_NORM_STD, False)}.get(names[-2])
    if leaf == "o_norm":
        return None
    if leaf == "embed":                     # [vocab, d_model]: unit rows
        return EMBED, False
    if leaf == "unembed":                   # [d_model, vocab]
        return LOGITS / math.sqrt(shape[0]), False
    if leaf == "router_bias":
        return BIAS_STD, False
    if leaf == "A_log":
        return A_LOG_STD, False
    if leaf == "g_bias":
        return G_BIAS_STD, False
    if leaf == "conv_w":                    # [taps, channels]
        return 1.0 / math.sqrt(shape[0]), False
    if names[-1] == "kernel":               # a projection: [fan_in, ...]
        if leaf not in ("q", "k", "v", "g", "beta", "gate", "kv_down", "o",
                        "up", "down", "shared_gate", "shared_up",
                        "shared_down"):
            raise KeyError(leaf)
        if leaf == "o":                     # [heads, v_head_dim, d_model]
            return O_GAIN / math.sqrt(shape[0] * shape[1]), False
        if leaf == "beta":
            return BETA_GAIN / math.sqrt(shape[0]), False
        return 1.0 / math.sqrt(shape[0]), False
    if leaf in ("router", "kv_up"):         # [d_model, experts], [R, H, ..]
        return 1.0 / math.sqrt(shape[0]), False
    if leaf in ("gate", "up", "down"):      # the experts': [held, in, out]
        return (DOWN_GAIN if leaf == "down" else 1.0) \
            / math.sqrt(shape[1]), False
    raise KeyError(leaf)


# ------------------------------------------------------------- the counts
# `m` below is the configuration file's dict (the model's published keys).
def _layers(m: dict) -> dict:
    of = kinds(m)
    return {k: of.count(k) for k in ("kda", "mla")}


def latent_row_values(m: dict) -> int:
    """What a position keeps a latent layer: the latent and the one key."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def _up_width(m: dict) -> int:
    return m["num_attention_heads"] * (m["qk_nope_head_dim"]
                                       + m["v_head_dim"])


def _kda_params(m: dict) -> int:
    """q, k, v, the gate's projection and o at hidden x 4096; beta and the
    head gate at hidden x heads; the taps, A, b and the output norm."""
    d, H, D = m["hidden_size"], m["num_attention_heads"], m["head_dim"]
    return 5 * d * H * D + 2 * d * H \
        + m["short_conv_kernel_size"] * 3 * H * D + H + H * D + D


def _latent_params(m: dict) -> int:
    d, H = m["hidden_size"], m["num_attention_heads"]
    return d * H * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) \
        + d * latent_row_values(m) + m["kv_lora_rank"] * _up_width(m) \
        + H * m["v_head_dim"] * d


def _swiglu_params(m: dict, width: int) -> int:
    return 3 * m["hidden_size"] * width


def layer_params(m: dict, i: int, experts: float) -> float:
    """Matmul parameters of layer i with `experts` routed experts counted."""
    mixer = _kda_params(m) if kinds(m)[i] == "kda" else _latent_params(m)
    if i < m["first_k_dense_replace"]:
        return mixer + _swiglu_params(m, m["intermediate_size"])
    return mixer + m["hidden_size"] * m["num_experts"] \
        + _swiglu_params(m, m["moe_shared_expert_intermediate_size"]) \
        + experts * _swiglu_params(m, m["moe_intermediate_size"])


def param_count(m: dict) -> float:
    """Every layer with the experts HELD here, the whole router and shared
    expert, both tables' slice. Norms' scales and the bias are below a
    thousandth, left out."""
    return sum(layer_params(m, i, m["num_local_experts"])
               for i in range(m["num_hidden_layers"])) \
        + 2 * m["vocab_size"] * m["hidden_size"]


def stored_param_bytes(m: dict, param_bytes: float) -> float:
    """Bytes of the weights as stored on the device."""
    return param_count(m) * param_bytes


def state_bytes(m: dict) -> float:
    """One "kda" layer's float32 state of one slot."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"] ** 2


def tail_bytes(m: dict) -> float:
    """One "kda" layer's float32 convolution's tail of one slot."""
    return 4.0 * (m["short_conv_kernel_size"] - 1) * 3 \
        * m["num_attention_heads"] * m["head_dim"]


KDA_CHUNK = 64       # the chunk the blocked form's products are counted at


def kda_scan_flops(m: dict, tokens: float) -> float:
    """Every "kda" layer's recurrence over `tokens` rows, counted as the
    blocked form's matrix products at chunks of KDA_CHUNK rows whatever form
    computes it: a row a head, six products of [K] x [K, V] or [K] x [K]
    rows against the state or to it (W S, q S, the state's update; U, W and
    the chunk's k . k and q . k at K each) and six of C x K inside the
    chunk: 6 K^2 + 6 C K multiply-adds counted as FLOPs."""
    H, D = m["num_attention_heads"], m["head_dim"]
    return _layers(m)["kda"] * tokens * H * (6.0 * D * D
                                              + 6.0 * KDA_CHUNK * D)


def kda_scan_bytes(m: dict, tokens: float, act_bytes: float) -> float:
    """The least every "kda" layer's recurrence moves for one tile of
    `tokens` rows: a row's q, k, v in and o out in the activations' type,
    its g in float32 and its beta; the state in and out once a tile."""
    H, D = m["num_attention_heads"], m["head_dim"]
    row = H * D * (4.0 * act_bytes + 4.0) + 4.0 * H
    return _layers(m)["kda"] * (tokens * row + 2.0 * state_bytes(m))


def kda_step_bytes(m: dict, rows: float) -> float:
    """The least every "kda" layer's one-row step moves for `rows` live
    slots: each slot's float32 state in and out."""
    return _layers(m)["kda"] * rows * 2.0 * state_bytes(m)


def mla_attend_flops(m: dict, pairs: float, rows: float) -> float:
    """The tiles' latent attention over `pairs` (query, key) pairs a latent
    layer, by `rows` rows (families/sarvam_mla.py `mla_attend_flops`, over
    this model's latent layers alone)."""
    H = m["num_attention_heads"]
    q_dim = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return _layers(m)["mla"] * (
        pairs * H * 2.0 * (q_dim + m["v_head_dim"])
        + rows * 2.0 * m["kv_lora_rank"] * _up_width(m))


def mla_attend_bytes(m: dict, keys: float, act_bytes: float) -> float:
    """The least the tiles' latent attention reads: the latents of the
    `keys` positions a tile attends, once a latent layer."""
    return _layers(m)["mla"] * keys * latent_row_values(m) * act_bytes


def mla_row_bytes(m: dict, live: float, kv_bytes: float) -> float:
    """The least the decode rows read of the latents: `live` positions a
    latent layer."""
    return _layers(m)["mla"] * live * latent_row_values(m) * kv_bytes


def mla_row_flops(m: dict, live: float) -> float:
    """The absorbed row's products over `live` positions a latent layer."""
    return _layers(m)["mla"] * live * m["num_attention_heads"] * 2.0 \
        * (latent_row_values(m) + m["kv_lora_rank"])


def causal_attention_flops(m: dict, batch: int, length: int,
                           backward: bool) -> float:
    """The one-shot path over a sequence: the latent layers' pairs and the
    recurrences; the backward twice the forward."""
    fwd = batch * (mla_attend_flops(m, causal_pairs(0, length), length)
                   + kda_scan_flops(m, length))
    return fwd * (3.0 if backward else 1.0)


def train_step_flops(m: dict, batch: int, length: int) -> float:
    """Useful forward + backward FLOPs of one training step of this share:
    6 a matmul parameter a token, with the routed experts a token uses that
    are held here, plus the mixers. (No cell trains this model: the scan has
    no tested backward, ROADMAP Reach B.)"""
    used = m["num_experts_per_tok"] * m["num_local_experts"] \
        / m["num_experts"]
    n = sum(layer_params(m, i, used) for i in range(m["num_hidden_layers"]))
    n += m["hidden_size"] * m["vocab_size"]
    return 6.0 * n * batch * length \
        + causal_attention_flops(m, batch, length, backward=True)


def decode_step_bytes(m: dict, live_lens: Iterable[float],
                      param_bytes: float, kv_bytes: float) -> float:
    """The LEAST one decode step must move: the weights as stored (the
    program's dispatch runs every held expert; only the unembedding half of
    the tables), every position of a live slot's latents in the latent
    layers, and its "kda" layers' float32 states in and out. `live_lens` is
    a length a live slot."""
    lens = [float(n) for n in live_lens]
    w = stored_param_bytes(m, param_bytes) \
        - m["vocab_size"] * m["hidden_size"] * param_bytes
    return w + mla_row_bytes(m, sum(lens), kv_bytes) \
        + kda_step_bytes(m, len(lens))
