"""The `minicpm_sala` family: MiniCPM-SALA's published keys mapped to the
program's `TransformerLM` with layers of two kinds in one stack
(models/transformer.py `mixer_kinds`): "minicpm4" layers, grouped-query
attention over blocks selected from mean-pooled keys
(models/sparse_attention.py `block_select`), and "lightning-attn" layers,
linear attention with a recurrent state (models/linear_attention.py); q/k
head norms and a sigmoid output gate on both, and the MiniCPM muP scalings.

What a family states is listed in families/mistral.py; this family's plain
reference is families/minicpm_sala_reference.py, its controls
families/minicpm_sala_controls.py, and its counts are the new
mathematics': a decode row moves each lightning layer's state in and out
and reads, of a sparse layer, the selected blocks of K and V and the
pooled keys.

Its comparison with the reference has THREE numbers a case (`scored`,
folded into the harness's one share by `folded`): each served token's gap
below its position's largest reference logit, as in the other families;
the case's `logit_deviation`, the program's own logits, teacher-forced on
the served tokens through the program's pools (`program_rows`), against the
reference's at the same positions; and its `state_deviation`, the first
lightning layer's state in that pool against the reference's. A count of
tokens and the logits' distance cannot see a fault smaller than bf16's own
rounding of the activations (a recurrent state kept in bf16 flips no more
tokens than the sound program does and moves the logits' distance by a
sixth); the state's own distance can (PERF.md section 6, PR 39).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Iterable

from perfbench.families import minicpm_sala_reference as reference
from perfbench.families.minicpm_sala_reference import (  # noqa: F401
    batch_loss, residual_scale)
from perfbench.spec import ROOT, SpecError

# ------------------------------------------------ configuration -> program
_MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "qk_norm": "qk_norm",
    "attn_use_rope": "attn_rope", "scale_emb": "scale_emb",
}
_KINDS = {"minicpm4": "blk", "lightning-attn": "lin"}
_SPARSE_KEYS = {"block_size": "blk_size", "kernel_size": "blk_kernel",
                "kernel_stride": "blk_stride", "init_blocks": "blk_init",
                "window_size": "blk_window", "topk": "blk_topk"}


def model_kwargs(cfg: dict) -> dict:
    """The configuration file as keyword arguments of TransformerConfig.
    Refuses what the program cannot state, or states otherwise."""
    def refuse(ok, why):
        if not ok:
            raise SpecError(why)
    refuse(os.path.isfile(os.path.join(
        ROOT, "ray_tpu", "models", "linear_attention.py")),
        "this checkout's program states no linear-attention layer "
        "(ray_tpu/models/linear_attention.py): it cannot run the family")
    refuse(cfg.get("hidden_act", "silu") == "silu",
           "the program's MLP is SwiGLU")
    refuse(not cfg.get("attention_bias"), "the program's projections have "
           "no bias")
    kinds = cfg["mixer_types"]
    refuse(len(kinds) == cfg["num_hidden_layers"]
           and not set(kinds) - set(_KINDS),
           f"mixer_types states one of {sorted(_KINDS)} for each layer")
    refuse(cfg["lightning_nh"] == cfg["lightning_nkv"]
           == cfg["num_attention_heads"]
           and cfg["lightning_head_dim"] == cfg["head_dim"],
           "the program's lightning layer has the attention's heads and "
           "head size, for q, k and v alike")
    refuse(cfg["lightning_use_rope"] and cfg["lightning_scale"] == "1/sqrt(d)"
           and cfg["use_output_norm"],
           "the program's lightning layer has rotary, the 1/sqrt(d) scale "
           "and an output norm")
    refuse(cfg["use_output_gate"] == cfg["attn_use_output_gate"],
           "the program gates both kinds of mixer or neither")
    sc = cfg["sparse_config"]
    engine = cfg.get("engine") or {}
    refuse(engine.get("max_len", 0) <= cfg["max_position_embeddings"],
           "the engine's slots pass max_position_embeddings")
    refuse(engine.get("max_len", 0) % sc["block_size"] == 0,
           "a slot holds whole blocks")
    refuse(engine.get("prefix_cache_slots", 0) == 0,
           "prefix blocks hold K and V only, not pooled keys or a state "
           "(inference/kv_cache.py BlockStore): prefix_cache_slots must be 0")
    kw = {dst: cfg[src] for src, dst in _MODEL_KEYS.items()}
    kw.update({dst: sc[src] for src, dst in _SPARSE_KEYS.items()})
    kw.update(mixer_kinds=[_KINDS[k] for k in kinds], scan_layers=False,
              out_gate=cfg["use_output_gate"],
              residual_scale=residual_scale(cfg),
              logit_scale=cfg["dim_model_base"] / cfg["hidden_size"],
              dtype="bfloat16",
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
    kw["mixer_kinds"] = tuple(kw["mixer_kinds"])
    return TransformerLM(TransformerConfig(**kw))


# ------------------------------------------------- against the reference
@functools.lru_cache(maxsize=2)
def _programs(model):
    """The model's cached forward as the engine's programs call it: a
    prefill tile into a scratch, and one decode row against the pools.
    (A control that plants a fault in a function these trace clears this
    cache: families/minicpm_sala_controls.py.)"""
    import jax
    return tuple(jax.jit(functools.partial(
        lambda chunked, params, toks, cache: model.apply(
            {"params": params}, toks, cache=cache, chunked_prefill=chunked),
        chunked)) for chunked in (True, False))


def program_rows(params, m: dict, prompt, generated, model=None):
    """(the PROGRAM's logits [len(generated), vocab], float32, at the
    positions `teacher_forced_gaps` scores, and the pool's lightning
    states [layers, H, D, D] after the last of them): the prompt prefilled in tiles
    of the engine's budget into a scratch of the program's own `SlotPool`
    (the tiles hand the lightning states on; the last tile's tail is rows
    no request owns), the scratch made the pool's one slot, then one decode
    row a served token, each reading and rewriting the pool: the engine's
    calls, with the served tokens fed in place of the sampled ones. The
    pools are the program's, in the types it keeps them in."""
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
            real=(jnp.arange(tile) < real)[None]))
        scratch = tuple(new[k] for k in names)
    rows = [lg[0, real - 1]]
    pool.insert(scratch, 0)
    del scratch, new
    for at in range(n, len(seq)):
        lg, new = row(params, jnp.asarray(seq[at:at + 1])[None], dict(
            zip(names, pool.pools()), idx=jnp.asarray([at], jnp.int32)))
        pool.rebind(tuple(new[k] for k in names))
        rows.append(lg[0, 0])
    return jnp.stack(rows).astype(jnp.float32), pool.s[:, 0]


def logit_deviation(rows, ref_rows):
    """(the median over the scored positions, each position's) root mean
    square over the vocabulary of the program's logit less the
    reference's. The median, so that a token whose 64th block fell the
    other way (a last bit; the token count's business) does not weigh."""
    import jax.numpy as jnp
    import numpy as np
    each = np.asarray(jnp.sqrt(jnp.mean(jnp.square(rows - ref_rows), -1)),
                      np.float64)
    return float(np.median(each)), each.tolist()


def state_deviation(states, ref_states):
    """[layers][H]: the distance of the pool's lightning state from the
    reference's after the same token, a head a layer, as a share of the
    reference's norm (Frobenius)."""
    import jax.numpy as jnp
    import numpy as np
    ref_states = jnp.stack(ref_states)
    off = jnp.sqrt(jnp.sum(jnp.square(states - ref_states), (-2, -1)))
    return np.asarray(off / jnp.sqrt(jnp.sum(jnp.square(ref_states),
                                             (-2, -1))), np.float64).tolist()


def scored(params, m: dict, prompt, generated, pad_to=None, program=None):
    """One case's numbers: `gaps` (each served token's gap below its
    position's largest reference logit), `spread` (the reference logits'),
    `logit_rms` and `logit_rms_each` (`logit_deviation` of the program's
    logits from the reference's), `state_rel` (`state_deviation`).
    `program`: what `program_rows` gave; computed here from `params` where
    not given."""
    gaps, spread, ref_rows, ref_states = reference.teacher_forced_gaps(
        params, m, prompt, generated, pad_to=pad_to, with_rows=True)
    rows, states = program or program_rows(params, m, prompt, generated)
    dev, each = logit_deviation(rows, ref_rows)
    return {"gaps": gaps, "spread": spread, "logit_rms": dev,
            "logit_rms_each": each,
            "state_rel": state_deviation(states, ref_states)}


def state_number(state_rel):
    """A case's state deviation as one number: the FIRST lightning layer's,
    root mean square over its slowest quarter of heads. The first layer of
    the stack reads the embedding alone, so the sound program's distance
    there is bf16's rounding of that layer's own k and v and nothing
    upstream (a later layer's also carries every flipped block before it);
    the slowest heads remember 76 to 256 tokens, over which a rounding of
    the state at every token adds up and a rounding of k and v does not."""
    first = state_rel[0]
    slow = first[-max(1, len(first) // 4):]
    return math.sqrt(sum(x * x for x in slow) / len(slow))


def folded(score: dict, tol: dict):
    """The case's numbers as the harness's one: where its logit deviation
    passes `logit_rms` or its state deviation `state_rel`, every token of
    the case counts as beyond the gap, at `logit_gap` x reading / limit.
    The harness compares a share of tokens within the gap (serve_cell.py):
    a case that fails a second number fails it by that case's whole
    share."""
    over = max(score["logit_rms"] / tol["logit_rms"],
               state_number(score["state_rel"]) / tol["state_rel"])
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
# Drawn so that each mechanism moves the logits at 1k-17k positions, which the
# program's own initialisers do not give (PERF.md section 6, PR 34 and PR 39).
# Every branch is damped by the model's own residual scale (0.2475) and adds
# about 0.12-0.15 an element to the residual, so the embedding's rows decide
# what share of the residual is the token itself and what share is context.
# Rows of 1 / scale_emb (a token enters the stack at 1.0 an element) left the
# token's own embedding 70% of the residual: a fault in three sparse layers
# moved the logits by about what bf16 rounding of that residual does, and no
# limit told the mildest control from the sound program (PERF.md section 6,
# PR 39). Rows of EMBED_STD / scale_emb (0.3 an element) leave the 24
# branches most of the residual, and the same faults read 4 to 10 times the
# sound program's count. Attention is peaked as a trained model's is: the q
# head norm's scale is drawn N(0, Q_NORM_STD^2), attention logits of that
# deviation, so a sparse layer's head gives a few positions' values and not
# the running mean of thousands (which would be next to nothing, and the same
# for every query: neither the selection nor a fault in it would reach the
# logits). A lightning layer takes the same draw: its output is normed a
# head, so the scale of q only weighs the head's dimensions. The unembedding
# is drawn wide because the model divides the final hidden by 16: logits of
# deviation 4 x UNEMBED_STD.
EMBED_STD, Q_NORM_STD, UNEMBED_STD = 0.3, 3.0, 0.3


def weight_rule(names, shape):
    """A leaf's draw: None for ones (the norms' scales but q_norm's), else
    (standard deviation, False: no leaf of this tree is a stack); an
    unknown leaf raises."""
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    if leaf == "scale":
        return (Q_NORM_STD, False) if names[-2] == "q_norm" else None
    if leaf == "embed":
        return EMBED_STD / 12.0, False                   # / scale_emb
    if leaf == "unembed":
        return UNEMBED_STD, False
    if leaf in ("q", "k", "v", "gate", "up", "down"):
        return 1.0 / math.sqrt(shape[0]), False          # [fan_in, ...]
    if leaf == "o":                                      # [heads, D, d_model]
        return 1.0 / math.sqrt(shape[0] * shape[1]), False
    raise KeyError(leaf)


# ------------------------------------------------------------- the counts
# `m` below is the configuration file's dict (the model's published keys).
def _n_of(m: dict, kind: str) -> int:
    return sum(k == kind for k in m["mixer_types"])


def _mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def layer_params(m: dict, kind: str) -> int:
    """Matmul parameters of one layer of `kind`: q, o and the gate are
    hidden x heads x head size; k and v as wide in a lightning layer, the
    KV heads' in a sparse one; the MLP."""
    d, hd = m["hidden_size"], m["head_dim"]
    wide = d * m["num_attention_heads"] * hd
    kv = wide if kind == "lightning-attn" \
        else d * m["num_key_value_heads"] * hd
    return 3 * wide + 2 * kv + _mlp_params(m)


def stored_param_bytes(m: dict, param_bytes: float) -> float:
    """Bytes of the weights as stored on the device: every layer of each
    kind, both tables. Norms' scales are below a thousandth and left out."""
    n = sum(layer_params(m, k) for k in m["mixer_types"])
    n += 2 * m["vocab_size"] * m["hidden_size"]
    return n * param_bytes


def selected_positions(m: dict, live: float) -> float:
    """Positions a query with `live` positions up to its own attends in a
    sparse layer: all of them up to topk blocks' worth, then topk blocks
    (the query's own block counted whole: within 63 positions)."""
    sc = m["sparse_config"]
    return min(float(live), float(sc["topk"] * sc["block_size"]))


def lightning_flops(m: dict, tokens: float) -> float:
    """One lightning layer over `tokens` tokens, by the recurrence: a head
    a token, D x D multiply-adds into the state and D x D out of it
    (4 D^2 FLOPs), whatever form computes it."""
    d = m["lightning_head_dim"]
    return 4.0 * d * d * m["lightning_nh"] * tokens


def lightning_bytes(m: dict, tokens: float, act_bytes: float) -> float:
    """The least one lightning layer moves for a tile of `tokens`: q, k, v
    in and o out in the activations' type, the float32 state in and out."""
    d, h = m["lightning_head_dim"], m["lightning_nh"]
    return 4.0 * tokens * h * d * act_bytes + 2.0 * h * d * d * 4.0


def blk_attend_flops(m: dict, selected: float) -> float:
    """One sparse layer's attention over `selected` (query, position)
    pairs in all: QK^T and AV, 2 FLOPs each a head a head dimension."""
    return 4.0 * selected * m["num_attention_heads"] * m["head_dim"]


def blk_attend_bytes(m: dict, tokens: float, positions: float,
                     act_bytes: float) -> float:
    """The least that attention moves for `tokens` queries over a cache of
    which `positions` are attended by some query: q in and o out, those K
    and V rows once."""
    return 2.0 * tokens * m["num_attention_heads"] * m["head_dim"] \
        * act_bytes + 2.0 * positions * m["num_key_value_heads"] \
        * m["head_dim"] * act_bytes


def causal_attention_flops(m: dict, batch: int, length: int,
                           backward: bool) -> float:
    """The one-shot path over a sequence: each sparse layer's attention
    over the positions each query selects, each lightning layer's
    recurrence; the backward twice the forward."""
    pairs = sum(selected_positions(m, t + 1) for t in range(length))
    fwd = batch * (_n_of(m, "minicpm4") * blk_attend_flops(m, pairs)
                   + _n_of(m, "lightning-attn") * lightning_flops(m, length))
    return fwd * (3.0 if backward else 1.0)


def train_step_flops(m: dict, batch: int, length: int) -> float:
    """Useful forward + backward FLOPs of one training step: 6 a matmul
    parameter a token, plus the mixers. (No cell trains this model: the
    lightning scan has no tested backward, ROADMAP Reach B.)"""
    n = sum(layer_params(m, k) for k in m["mixer_types"])
    n += m["hidden_size"] * m["vocab_size"]
    return 6.0 * n * batch * length \
        + causal_attention_flops(m, batch, length, backward=True)


def decode_step_bytes(m: dict, live_lens: Iterable[float],
                      param_bytes: float, kv_bytes: float) -> float:
    """The LEAST one decode step must move: the weights as stored (only
    the unembedding half of the tables), each row's float32 state of each
    lightning layer in and out, and for each sparse layer a row's selected
    positions of K and V and its pooled keys, one every `kernel_stride`
    positions. `live_lens` is a length a live slot (the reader
    metrics/decode_roofline_share.tok.py hands each request's)."""
    sc = m["sparse_config"]
    lens = [float(n) for n in live_lens]
    w = stored_param_bytes(m, param_bytes) \
        - m["vocab_size"] * m["hidden_size"] * param_bytes
    kv_row = m["num_key_value_heads"] * m["head_dim"] * kv_bytes
    state = 2.0 * m["lightning_nh"] * m["lightning_head_dim"] ** 2 * 4.0
    rows = sum(2.0 * selected_positions(m, n) * kv_row
               + n / sc["kernel_stride"] * kv_row for n in lens)
    return w + _n_of(m, "minicpm4") * rows \
        + _n_of(m, "lightning-attn") * state * len(lens)
