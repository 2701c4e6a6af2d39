"""The `afmoe` family: Trinity-Large-Preview's published keys mapped to the
program's `TransformerLM` with layers of the kinds "win" and "att"
(models/transformer.py): grouped-query attention with q/k head norms and a
sigmoid output gate, over the 4,096 newest positions out of a RING in the
sliding layers (rotary) and over every position of a cache by position in
the full ones (no rotary); four norms a block; leading dense layers; an
expert layer routed by sigmoid scores with a selection bias, a shared
expert, and one rank's share of the routed experts (models/moe.py).

What a family states is listed in families/mistral.py; this family's plain
reference is families/afmoe_reference.py, its controls
families/afmoe_controls.py, and its counts are the new mathematics': a
sliding layer's decode row reads min(length, window) positions whatever the
slot's length, the weights stored are the held experts'.

Its comparison with the reference has THREE numbers a case (`scored`,
folded into the harness's one share by `folded`): each served token's gap
below its position's largest reference logit, as in the other families; the
case's `logit_rms`, the program's own logits, teacher-forced on the served
tokens through the program's own one-slot `SlotPool` (`program_rows`: tile
by tile into the ring, the scratch made the slot, row by row out of it),
against the reference's at the same positions: the median over positions,
which a token whose top-4 flips between bf16 and float32 does not move; and
its `route_rel` (`route_deviation`): the program's first expert layer on the
served weights and seeded probe rows, computed in float32, against the
reference's. A selection bias that keeps the experts' loads even is a
hundredth of a score, so weighing by it moves a weight by half a per cent,
under bf16's own rounding of the activations: the logits cannot see it,
the layer's own arithmetic in float32 can.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Iterable

from perfbench.families import afmoe_reference as reference
from perfbench.families.afmoe_reference import (  # noqa: F401
    FULL, SLIDING, batch_loss, experts_first)
from perfbench.families.falcon_h1 import logit_deviation
from perfbench.spec import ROOT, SpecError

# ------------------------------------------------ configuration -> program
_MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "moe_intermediate_size": "expert_d_ff",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "num_experts": "n_experts", "num_experts_per_tok": "expert_top_k",
    "num_shared_experts": "n_shared_experts",
    "num_dense_layers": "n_dense_layers", "sliding_window": "window",
    "route_norm": "route_norm", "route_scale": "route_scale",
}
KEY_BLOCK = 512      # a tile's attention walks ring and scratch in such blocks
_KINDS = {SLIDING: "win", FULL: "att"}


def ring_positions(cfg: dict) -> int:
    """A sliding layer's ring: the window and the largest tile beside it."""
    return cfg["sliding_window"] + cfg["engine"]["prefill_budget"]


def model_kwargs(cfg: dict) -> dict:
    """The configuration file as keyword arguments of TransformerConfig.
    Refuses what the program cannot state, or states otherwise."""
    def refuse(ok, why):
        if not ok:
            raise SpecError(why)
    with open(os.path.join(ROOT, "ray_tpu", "models",
                           "transformer.py")) as f:
        refuse("CACHE_RINGS" in f.read(),
               "this checkout's program states no sliding window kept in a "
               "ring (ray_tpu/models/transformer.py CACHE_RINGS): it cannot "
               "run the family")
    refuse(cfg.get("hidden_act", "silu") == "silu",
           "the program's MLP and experts are SwiGLU")
    refuse(cfg.get("score_func") == "sigmoid" and cfg.get("n_group", 1) == 1
           and cfg.get("topk_group", 1) == 1,
           "the family's router is a sigmoid over ungrouped experts")
    refuse(cfg.get("rope_scaling") is None,
           "the program states no rope scaling")
    refuse(len(cfg["layer_types"]) == cfg["num_hidden_layers"]
           and set(cfg["layer_types"]) <= set(_KINDS),
           f"layer_types: one of {sorted(_KINDS)} a layer")
    first, held = experts_first(cfg), cfg["num_local_experts"]
    refuse(0 < held and first + held <= cfg["num_experts"],
           f"experts {first}..{first + held} are not among the router's "
           f"{cfg['num_experts']}")
    engine = cfg.get("engine") or {}
    refuse(engine.get("max_len", 0) <= cfg["max_position_embeddings"],
           "the engine's slots pass max_position_embeddings")
    block = min(KEY_BLOCK, engine["prefill_budget"])
    refuse(ring_positions(cfg) % block == 0
           and (engine["max_len"] + engine["prefill_budget"]) % block == 0,
           f"the ring, and a slot with the largest tile, hold whole blocks "
           f"of {KEY_BLOCK} keys")
    refuse(engine.get("prefix_cache_slots", 0) == 0
           and not engine.get("spec"),
           "prefix blocks and a draft's verify step hold K and V by "
           "position only, not a ring (inference/kv_cache.py BlockStore): "
           "prefix_cache_slots must be 0 and spec absent")
    kw = {dst: cfg[src] for src, dst in _MODEL_KEYS.items()}
    kw.update(rope_theta=float(cfg["rope_theta"]),
              mixer_kinds=[_KINDS[k] for k in cfg["layer_types"]],
              win_ring=ring_positions(cfg), attn_rope=False, qk_norm=True,
              out_gate=True, sandwich_norm=True, router="sigmoid",
              scale_emb=math.sqrt(cfg["hidden_size"])
              if cfg.get("mup_enabled") else 1.0,
              experts_held=[first, held], scan_layers=False,
              dtype="bfloat16", param_dtype=cfg.get("param_dtype",
                                                    "bfloat16"))
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
    and one decode row against the pools. (A control that plants a fault
    in a function these trace clears this cache: families/
    afmoe_controls.py.)"""
    import jax

    def forward(chunked, params, toks, cache, rows=None):
        return model.apply({"params": params}, toks, cache=cache,
                           chunked_prefill=chunked, logit_rows=rows)

    return (jax.jit(functools.partial(forward, True)),
            jax.jit(functools.partial(forward, False)))


def program_rows(params, m: dict, prompt, generated, model=None):
    """What the PROGRAM computes for one case, teacher-forced on the served
    tokens through its own one-slot `SlotPool`: the prompt prefilled in
    tiles of the engine's budget into a scratch (the sliding layers' K and
    V into its rings, wrapping as they fill), the scratch made the pool's
    one slot, then one decode row a served token, each reading and writing
    the pool where it lies: the engine's calls, with the served tokens fed
    in place of the sampled ones. -> its logits [len(generated), vocab],
    float32, at the positions `teacher_forced_gaps` scores, as "rows";
    beside them "route_rel" (`route_deviation` of the same model)."""
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
    of the reference's norm. Scores, the bias's choice, the weights, their
    norm and scale, the held experts and the shared one all enter. Not the
    median: three rows of five pick no held expert, and a fault in the
    weights leaves those as they were; not the largest: a row whose top-4
    flips at a near-tie must not decide."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.moe import MoEMLP
    p = params[f"layer_{m['num_dense_layers']}"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(46),
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


def folded(score: dict, tol: dict):
    """The case's numbers as the harness's one: where its logit deviation
    passes `logit_rms` or its expert layer's `route_rel`, every token of
    the case counts as beyond the gap, at `logit_gap` x reading / limit.
    The harness compares a share of tokens within the gap (serve_cell.py):
    a case that fails a further number fails it by that case's whole
    share."""
    over = max(score["logit_rms"] / tol["logit_rms"],
               score["route_rel"] / tol["route_rel"])
    if over <= 1.0:
        return score["gaps"]
    return [max(g, tol["logit_gap"] * over) for g in score["gaps"]]


def teacher_forced_gaps(params, m: dict, prompt, generated, pad_to=None,
                        with_spread=False, program=None):
    """What the harness asks of a family (replica.bench_reference): a gap
    a served token, `scored` and `folded`."""
    score = scored(params, m, prompt, generated, pad_to, program)
    gaps = folded(score, m["reference_tolerance"])
    return (gaps, score["spread"]) if with_spread else gaps


# ---------------------------------------------------------- seeded weights
# Drawn so that the seeded model is not degenerate at 12k-19k positions
# (PERF.md section 6, PR 34 and PR 46). What every position has in common
# passes attention whole while what is a position's own is averaged down
# over the positions a head attends, and a post-norm then scales the branch
# back to 1 an element: with attention logits of deviation 2 (75 of a
# window's 4,096 positions a head, 330 of a full layer's 18k) the share of
# the hidden states' energy that all positions share grows 0.6% -> 11% ->
# 40% over the first three layers (reckoned, and read on the chip as the
# overflow route taken in 93 of 100 layer-tiles: a router's score shifts by
# the common part's projection, a bias of deviation sqrt(share) that no
# capacity of twice the mean holds). So:
# - the q head norm's scale is drawn N(0, Q_NORM_STD^2) with deviation 3:
#   attention logits of deviation 3, a head attends one to three positions
#   anywhere in its window, nothing is averaged down and the shared part
#   stays under a thousandth;
# - the post-attention norm's scale is drawn N(0, ATTN_BRANCH^2): the
#   branch joins the residual at half an element, the token's own embedding
#   (EMBED after the muP factor), the dense MLP and the experts at 1;
# - the router's scores are sigmoid of unit normals (the four taken read
#   0.90-0.96, the fourth and the fifth 0.008 apart) and the selection bias
#   N(0, BIAS_STD^2): not zero, so that choosing and weighing are told
#   apart (`route_rel`), and no larger, because with random routers a bias
#   of 0.02 already doubles the load of the experts it favours (a trained
#   bias evens loads, a drawn one cannot; simulated before any chip run:
#   the fullest of 32 held experts takes 26-34 of a group's 4,160 picks at
#   0.005, 40-57 at 0.02, against a capacity of 33 at `capacity_factor`
#   2.0; the configuration takes 3.0, 49 rows: PERF.md section 6);
# - logits of deviation LOGITS.
EMBED, Q_NORM_STD, ATTN_BRANCH, BIAS_STD, LOGITS = 1.0, 3.0, 0.5, 0.005, 1.2


def weight_rule(names, shape):
    """A leaf's draw: None for ones (the norms' scales but q_norm's and the
    post-attention norm's), else
    (standard deviation, False: no leaf of this tree is a stack); an
    unknown leaf raises."""
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    if leaf == "scale":
        return {"q_norm": (Q_NORM_STD, False),
                "post_attn_norm": (ATTN_BRANCH, False)}.get(names[-2])
    if leaf == "embed":                     # [vocab, d_model]
        return EMBED / math.sqrt(shape[1]), False
    if leaf == "unembed":                   # [d_model, vocab]
        return LOGITS / math.sqrt(shape[0]), False
    if leaf == "router_bias":
        return BIAS_STD, False
    if names[-1] == "kernel":               # a projection: [fan_in, ...]
        if leaf not in ("q", "k", "v", "o", "gate", "up", "down",
                        "shared_gate", "shared_up", "shared_down"):
            raise KeyError(leaf)
        # (o contracts heads and head size: [heads, D, d_model])
        return 1.0 / math.sqrt(shape[0] * (shape[1] if leaf == "o" else 1)
                               ), False
    if leaf == "router":                    # [d_model, experts]
        return 1.0 / math.sqrt(shape[0]), False
    if leaf in ("gate", "up", "down"):      # the experts': [held, in, out]
        return 1.0 / math.sqrt(shape[1]), False
    raise KeyError(leaf)


# ------------------------------------------------------------- the counts
# `m` below is the configuration file's dict (the model's published keys).
def _attn_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    return 3 * d * m["num_attention_heads"] * hd \
        + 2 * d * m["num_key_value_heads"] * hd     # q, o, gate and k, v


def _swiglu_params(m: dict, width: int) -> int:
    return 3 * m["hidden_size"] * width


def layer_params(m: dict, i: int, experts: float) -> float:
    """Matmul parameters of layer i with `experts` routed experts counted."""
    if i < m["num_dense_layers"]:
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


def _layers(m: dict, kind: str) -> int:
    return sum(k == kind for k in m["layer_types"])


def _kv_row_bytes(m: dict, kv_bytes: float) -> float:
    """K and V of one position of one layer."""
    return 2.0 * m["num_key_value_heads"] * m["head_dim"] * kv_bytes


def window_pairs(m: dict, pos0: float, rows: float) -> float:
    """(query, key) pairs of `rows` rows at positions pos0 .. in ONE
    sliding layer: row t attends min(t + 1, window) keys."""
    W = m["sliding_window"]
    full = max(0.0, min(rows, pos0 + rows - W + 1))     # rows past the window
    rising = rows - full
    return full * W + rising * (pos0 + 1 + pos0 + rising) / 2.0


def win_attend_flops(m: dict, pairs: float) -> float:
    """QK^T and AV of the sliding layers over `pairs` (query, key) pairs a
    layer: 2 matmuls x 2 FLOP x heads x head size each."""
    return _layers(m, SLIDING) * 4.0 * pairs \
        * m["num_attention_heads"] * m["head_dim"]


def win_attend_bytes(m: dict, rows: float, keys: float,
                     act_bytes: float) -> float:
    """The least the sliding layers' attention moves for one tile of `rows`
    rows that attend `keys` distinct positions: K and V of those once, q in
    and the output out."""
    qo = 2.0 * rows * m["num_attention_heads"] * m["head_dim"] * act_bytes
    return _layers(m, SLIDING) * (keys * _kv_row_bytes(m, act_bytes) + qo)


def win_row_bytes(m: dict, live: float, kv_bytes: float) -> float:
    """The least the sliding layers' decode rows move: `live`, the sum over
    the step's live slots of min(length, window), positions of K and V a
    layer."""
    return _layers(m, SLIDING) * live * _kv_row_bytes(m, kv_bytes)


def causal_attention_flops(m: dict, batch: int, length: int,
                           backward: bool) -> float:
    """The one-shot path over a sequence: each full layer's causal
    attention over the pairs at or below the diagonal, each sliding layer's
    over those within the window; the backward twice the forward."""
    pairs = length * (length + 1) / 2.0
    fwd = batch * (_layers(m, FULL) * 4.0 * pairs
                   * m["num_attention_heads"] * m["head_dim"]
                   + win_attend_flops(m, window_pairs(m, 0, length)))
    return fwd * (3.0 if backward else 1.0)


def train_step_flops(m: dict, batch: int, length: int) -> float:
    """Useful forward + backward FLOPs of one training step of this share:
    6 a matmul parameter a token, with the routed experts a token uses
    that are held here (num_experts_per_tok x held / all, on average),
    plus attention. (No cell trains this model.)"""
    used = m["num_experts_per_tok"] * m["num_local_experts"] \
        / m["num_experts"]
    n = sum(layer_params(m, i, used)
            for i in range(m["num_hidden_layers"]))
    n += m["hidden_size"] * m["vocab_size"]
    return 6.0 * n * batch * length \
        + causal_attention_flops(m, batch, length, backward=True)


def decode_step_bytes(m: dict, live_lens: Iterable[float],
                      param_bytes: float, kv_bytes: float) -> float:
    """The LEAST one decode step must move: the weights as stored (the
    program's dispatch runs every held expert; only the unembedding half of
    the tables), and a live slot's K and V: every position of a full layer,
    min(length, window) of a sliding one. `live_lens` is a length a live
    slot (the reader metrics/decode_roofline_share.tok.py hands each
    request's)."""
    lens = [float(n) for n in live_lens]
    w = stored_param_bytes(m, param_bytes) \
        - m["vocab_size"] * m["hidden_size"] * param_bytes
    W = m["sliding_window"]
    return w + _kv_row_bytes(m, kv_bytes) * (
        _layers(m, FULL) * sum(lens)
        + _layers(m, SLIDING) * sum(min(n, W) for n in lens))
