"""The `falcon_h1` family: Falcon-H1's published keys mapped to the program's
`TransformerLM` with every layer of the kind "hyb" (models/transformer.py):
grouped-query attention heads and a Mamba-2 state-space mixer
(models/ssm.py) in parallel on one normed input, their outputs summed under
the published multipliers, so that one layer keeps K and V by position AND
two states with none (the mixer's, and its convolution's input's tail).

What a family states is listed in families/mistral.py; this family's plain
reference is families/falcon_h1_reference.py, its controls
families/falcon_h1_controls.py, and its counts are the new mathematics': a
decode row moves each layer's state and tail in and out whatever the slot's
length, beside the live K and V.

Its comparison with the reference has FIVE numbers a case (`scored`, folded
into the harness's one share by `folded`): each served token's gap below
its position's largest reference logit, as in the other families; the
case's `logit_rms`, the program's own logits, teacher-forced on the served
tokens through the program's own one-slot `SlotPool` (`program_rows`),
against the reference's at the same positions; its `edge_rms`, the same at
the three rows that open every prefill tile after the first, whose
convolution reads the tile before's tail (a tail lost between two tiles is
three rows of a thousand wrong and forgotten by the scored tokens; at those
rows it is the whole of the branch); its `state_rel`, the FIRST layer's
state in that pool against the reference's, right after `insert` and after
the last scored token; and its `tail_rel`, the first layer's convolution's
tail at the same two points (after `insert` it is what the last, padded
tile handed the slot). A count of tokens cannot see a fault smaller than
bf16's own rounding of the activations (a state kept in bf16); the state's
own distance can (PERF.md section 6, PR 39 and PR 44).
"""

from __future__ import annotations

import functools
import json
import math
import os
from typing import Iterable

from perfbench.families import falcon_h1_reference as reference
from perfbench.families.falcon_h1_reference import batch_loss  # noqa: F401
from perfbench.spec import ROOT, SpecError

# ------------------------------------------------ configuration -> program
_MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
    "mamba_d_state": "ssm_state", "mamba_n_groups": "ssm_groups",
    "mamba_d_conv": "ssm_conv",
    "embedding_multiplier": "scale_emb", "lm_head_multiplier": "logit_scale",
    "attention_in_multiplier": "attn_in_mult",
    "attention_out_multiplier": "attn_out_mult",
    "key_multiplier": "key_mult", "ssm_in_multiplier": "ssm_in_mult",
    "ssm_out_multiplier": "ssm_out_mult", "ssm_multipliers": "ssm_mults",
    "mlp_multipliers": "mlp_mults",
}
KEY_BLOCK = 512      # the tile's attention walks its scratch in such blocks


def model_kwargs(cfg: dict) -> dict:
    """The configuration file as keyword arguments of TransformerConfig.
    Refuses what the program cannot state, or states otherwise."""
    def refuse(ok, why):
        if not ok:
            raise SpecError(why)
    refuse(os.path.isfile(os.path.join(ROOT, "ray_tpu", "models", "ssm.py")),
           "this checkout's program states no state-space mixer "
           "(ray_tpu/models/ssm.py): it cannot run the family")
    refuse(cfg.get("hidden_act", "silu") == "silu",
           "the program's MLP is SwiGLU")
    refuse(not any(cfg.get(k) for k in (
        "attention_bias", "mamba_proj_bias", "mlp_bias", "projectors_bias")),
        "the program's projections have no bias")
    refuse(cfg["mamba_conv_bias"] and cfg["mamba_rms_norm"]
           and not cfg["mamba_norm_before_gate"],
           "the program's mixer has a convolution with bias and gates "
           "before its grouped RMSNorm")
    refuse(cfg["mamba_d_ssm"] == cfg["mamba_n_heads"] * cfg["mamba_d_head"]
           and cfg["mamba_n_heads"] % cfg["mamba_n_groups"] == 0,
           "mamba_d_ssm is mamba_n_heads x mamba_d_head, in whole groups")
    refuse(cfg.get("rope_scaling") is None
           and cfg.get("attn_layer_indices") is None,
           "the program states no rope scaling, and attention in every "
           "layer")
    engine = cfg.get("engine") or {}
    refuse(engine.get("max_len", 0) <= cfg["max_position_embeddings"],
           "the engine's slots pass max_position_embeddings")
    refuse((engine.get("max_len", 0) + engine.get("prefill_budget", 0))
           % KEY_BLOCK == 0,
           f"a slot and the largest tile together hold whole blocks of "
           f"{KEY_BLOCK} keys (the tile's attention walks the scratch in "
           f"the largest power of two that divides it)")
    refuse(engine.get("prefix_cache_slots", 0) == 0
           and not engine.get("spec"),
           "prefix blocks and a draft's verify step hold K and V only, not "
           "a state or a convolution's tail (inference/kv_cache.py "
           "BlockStore): prefix_cache_slots must be 0 and spec absent")
    kw = {dst: cfg[src] for src, dst in _MODEL_KEYS.items()}
    # (1e11 as an integer passes 32 bits, which a jitted power refuses)
    kw.update(rope_theta=float(cfg["rope_theta"]),
              mixer_kinds=["hyb"] * cfg["num_hidden_layers"],
              scan_layers=False, dtype="bfloat16",
              param_dtype=cfg.get("param_dtype", "bfloat16"))
    return kw


def build_model(kw: dict):
    """In a process that may import JAX: kwargs -> the flax module."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import TransformerConfig
    kw = dict(kw)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    for key in ("mixer_kinds", "ssm_mults", "mlp_mults"):
        kw[key] = tuple(kw[key])
    return TransformerLM(TransformerConfig(**kw))


# ------------------------------------------------- against the reference
@functools.lru_cache(maxsize=2)
def _programs(model):
    """The model's cached forward as the engine's programs call it: a
    prefill tile into a scratch (its final-norm rows: the tile's logits
    [1024, 261120] in float32 are 1.07 GB beside 13.4 GB held), one decode
    row against the pools, and the program's own unembedding of the rows
    asked for. (A control that plants a fault in a function these trace
    clears this cache: families/falcon_h1_controls.py.)"""
    import jax

    def forward(chunked, hidden, params, toks, cache):
        return model.apply({"params": params}, toks, cache=cache,
                           chunked_prefill=chunked, return_hidden=hidden)

    def head(params, h):
        return model.apply({"params": params}, h, params["embed"],
                           params.get("unembed"), method=type(model)._logits)

    return (jax.jit(functools.partial(forward, True, True)),
            jax.jit(functools.partial(forward, False, False)),
            jax.jit(head))


EDGE = 3             # rows at a tile's start that read the tile before's tail


def program_rows(params, m: dict, prompt, generated, model=None):
    """What the PROGRAM computes for one case, teacher-forced on the served
    tokens through its own one-slot `SlotPool`: the prompt prefilled in
    tiles of the engine's budget into a scratch (the tiles hand state and
    tail on; the last tile's tail is rows no request owns), the scratch
    made the pool's one slot, then one decode row a served token, each
    reading and rewriting the pool: the engine's calls, with the served
    tokens fed in place of the sampled ones. -> {"rows": its logits
    [len(generated), vocab], float32, at the positions
    `teacher_forced_gaps` scores; "edge": the positions of the first
    `EDGE` rows of every tile after the first, the rows whose convolution
    reads the tile before's tail, and "edge_rows", its logits there;
    "states" [2, layers, H, P, N] and "tails" [2, layers, K - 1,
    channels]: the pool's, in the types it keeps them in, right after
    `insert` and after the last scored token}."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.inference import kv_cache
    if model is None:
        model = build_model(model_kwargs(m))
    tile, max_len = m["engine"]["prefill_budget"], m["engine"]["max_len"]
    pool = kv_cache.SlotPool(model.cfg, 1, max_len, max_len,
                             max_len + tile, model.cfg.dtype)
    names = tuple(pool.shapes)
    tiled, row, head = _programs(model)
    seq = np.asarray(list(prompt) + list(generated)[:-1], np.int32)
    n = len(prompt)
    scratch = pool.new_scratch()
    edge, edge_rows = [], []
    for at in range(0, n, tile):
        real = min(tile, n - at)
        toks = np.zeros((1, tile), np.int32)
        toks[0, :real] = seq[at:at + real]
        h, new = tiled(params, jnp.asarray(toks), dict(
            zip(names, scratch), idx=jnp.int32(at),
            real=(jnp.arange(tile) < real)[None]))
        scratch = tuple(new[k] for k in names)
        if at:
            edge += range(at, at + min(EDGE, real))
            edge_rows.append(head(params, h[:, :min(EDGE, real)])[0])
    rows = [head(params, h[:, real - 1:real])[0, 0]]
    del h
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
            "tails": jnp.stack([c for _, c in held])}


def logit_deviation(rows, ref_rows):
    """(the median over the positions, each position's) root mean square
    over the vocabulary of the program's logit less the reference's."""
    import jax.numpy as jnp
    import numpy as np
    each = np.asarray(jnp.sqrt(jnp.mean(jnp.square(rows - ref_rows), -1)),
                      np.float64)
    return float(np.median(each)), each.tolist()


def state_deviation(state, ref_state):
    """[.., H]: the distance of a layer's state in the pool from the
    reference's after the same token, a head, as a share of the
    reference's norm (Frobenius)."""
    import jax.numpy as jnp
    import numpy as np
    off = jnp.sqrt(jnp.sum(jnp.square(state - ref_state), (-2, -1)))
    return np.asarray(off / jnp.sqrt(jnp.sum(jnp.square(ref_state),
                                             (-2, -1))), np.float64)


def tail_deviation(tail, ref_tail):
    """[..]: the distance of a layer's convolution's tail from the
    reference's, as a share of the reference's norm."""
    import jax.numpy as jnp
    import numpy as np
    return np.asarray(jnp.sqrt(
        jnp.sum(jnp.square(tail - ref_tail), (-2, -1))
        / jnp.sum(jnp.square(ref_tail), (-2, -1))), np.float64)


def scored(params, m: dict, prompt, generated, pad_to=None, program=None):
    """One case's numbers: `gaps` (each served token's gap below its
    position's largest reference logit), `spread` (the reference logits'),
    `logit_rms` and `logit_rms_each` (`logit_deviation` at the scored
    positions), `edge_rms` (the same at the rows that open a tile after
    the first: 0.0 for a prompt of one tile), `state_rel`
    (`state_deviation` of the FIRST layer, a head, the larger of right
    after `insert` and after the last scored token) and `tail_rel`
    (`tail_deviation` of the first layer, the larger of the same two).
    `program`: what `program_rows` gave; computed here from `params` where
    not given."""
    import numpy as np
    got = program or program_rows(params, m, prompt, generated)
    ref = reference.teacher_forced_gaps(params, m, prompt, generated,
                                        pad_to=pad_to, with_rows=True,
                                        also=got["edge"])
    dev, each = logit_deviation(got["rows"], ref["rows"])
    edge = logit_deviation(got["edge_rows"], ref["also"])[0] \
        if got["edge"] else 0.0
    return {"gaps": ref["gaps"], "spread": ref["spread"], "logit_rms": dev,
            "logit_rms_each": each, "edge_rms": edge,
            "state_rel": np.max(state_deviation(
                got["states"][:, 0], ref["states"][0]), 0).tolist(),
            "tail_rel": float(np.max(tail_deviation(
                got["tails"][:, 0], ref["tails"][0])))}


def state_number(state_rel) -> float:
    """A case's state deviation as one number: the LARGEST over the first
    layer's heads. The first layer reads the embedding alone, so the sound
    program's distance there is bf16's rounding of that layer's own x, B,
    C and dt and nothing upstream, 0.001-0.011 a head whatever its memory;
    a state kept coarse between calls adds a rounding a token, which a
    head that forgets in a few tokens sheds and a head that remembers
    hundreds piles up (0.04-0.10): the root mean square over all 32 heads
    read 0.005 against 0.009-0.018, the largest 0.006-0.011 against
    0.036-0.098 (my chip runs, PR 44)."""
    return max(state_rel)


def folded(score: dict, tol: dict):
    """The case's numbers as the harness's one: where its logit deviation
    (at the scored positions, or at the rows that open a tile) passes
    `logit_rms`, its state deviation `state_rel` or its tail's
    `tail_rel`, every token of the case counts as beyond the gap, at
    `logit_gap` x reading / limit. The harness compares a share of tokens
    within the gap (serve_cell.py): a case that fails a further number
    fails it by that case's whole share."""
    over = max(score["logit_rms"] / tol["logit_rms"],
               score["edge_rms"] / tol["logit_rms"],
               state_number(score["state_rel"]) / tol["state_rel"],
               score["tail_rel"] / tol["tail_rel"])
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
# Drawn so that BOTH branches and the MLP reach the logits under the
# published multipliers, which plain 1 / sqrt(fan_in) draws do not give: the
# model was trained WITH its multipliers, so its weights are as much larger
# as the multipliers are small, and a plain draw leaves the attention heads
# 0.0375, the mixer 0.088 and the MLP 0.005 an element of a residual whose
# token's own embedding is 5.7 (configs/falcon-h1-34b.json `assumed` has the
# readings). Each draw is a GAIN over 1 / sqrt(fan_in) that undoes the
# multipliers on its path, so that with a normed input of 1 an element:
# - each of a layer's three branches adds about BRANCH an element to the
#   residual and the embedding's rows are EMBED an element after
#   `embedding_multiplier`: after six layers the token's own embedding is a
#   twentieth of the residual's energy and context the rest;
# - q and k are drawn so that attention logits have deviation ATTN_LOGITS
#   (peaked, as a trained model's: a flat softmax over thousands of
#   positions is the running mean of their values, next to nothing, and the
#   same for every query);
# - z, x, B, C and dt leave the in-projection at about 1, 1, 0.7, 2 and 1
#   an element (x, B and C are one matrix and share a draw);
# - `A_log` ~ N(0, A_LOG_STD^2) and `dt_bias` ~ N(0, DT_BIAS_STD^2): the
#   draw is a zero-mean normal (perfbench/weights.py), so the spread of
#   Mamba-2's initialisation (dt log-uniform in 0.001-0.1, A uniform in
#   1-16) is had as a spread of dt x A over heads: a head forgets by
#   exp(-softplus(dt) exp(A_log)) a token, from within one token to a few
#   thousand over a layer's 32 heads;
# - logits of deviation LOGITS.
EMBED, BRANCH, ATTN_LOGITS, LOGITS = 0.3, 0.3, 3.0, 1.2
A_LOG_STD, DT_BIAS_STD = 2.5, 2.0
# the published multipliers the gains undo, read from the family's one
# configuration (`weight_rule` is handed a leaf's name and shape, no
# configuration; the forward pass reads the same keys through
# `model_kwargs`)
with open(os.path.join(ROOT, "perfbench", "configs",
                       "falcon-h1-34b.json")) as _f:
    _PUBLISHED = json.load(_f)
_ATTN_OUT, _KEY, _SSM_IN, _SSM_OUT, _EMBEDDING, _LM_HEAD = (
    _PUBLISHED[k + "_multiplier"] for k in (
        "attention_out", "key", "ssm_in", "ssm_out", "embedding", "lm_head"))
_SSM_MULTS, _MLP_MULTS = (_PUBLISHED["ssm_multipliers"],
                          _PUBLISHED["mlp_multipliers"])
_GAINS = {
    # attention logits: gain^2 x key_multiplier = ATTN_LOGITS
    "q": math.sqrt(ATTN_LOGITS / _KEY), "k": math.sqrt(ATTN_LOGITS / _KEY),
    "v": 1.0, "o": BRANCH / _ATTN_OUT,
    "in_z": 1.0 / (_SSM_IN * _SSM_MULTS[0]),
    "in_xbc": 1.0 / (_SSM_IN * _SSM_MULTS[1]),
    "in_dt": 1.0 / (_SSM_IN * _SSM_MULTS[4]),
    "out": BRANCH / _SSM_OUT,
    "gate": 1.0 / _MLP_MULTS[0], "up": 1.0,
    # silu(g) * u of two unit normals is 0.45 an element
    "down": BRANCH / (_MLP_MULTS[1] * 0.45),
}


def weight_rule(names, shape):
    """A leaf's draw: None for ones (the norms' scales and D), else
    (standard deviation, False: no leaf of this tree is a stack); an
    unknown leaf raises."""
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    if leaf in ("scale", "norm_scale", "D"):
        return None
    if leaf == "embed":
        return EMBED / _EMBEDDING, False
    if leaf == "unembed":                   # [d_model, vocab]
        return LOGITS / (_LM_HEAD * math.sqrt(shape[0])), False
    if leaf == "A_log":
        return A_LOG_STD, False
    if leaf == "dt_bias":
        return DT_BIAS_STD, False
    if leaf == "conv_w":                    # [taps, channels]: unit output
        return 1.0 / math.sqrt(shape[0]), False
    if leaf == "conv_b":
        return 0.1, False
    if leaf == "o":                         # [heads, D, d_model]
        return _GAINS[leaf] / math.sqrt(shape[0] * shape[1]), False
    return _GAINS[leaf] / math.sqrt(shape[0]), False     # [fan_in, ...]


# ------------------------------------------------------------- the counts
# `m` below is the configuration file's dict (the model's published keys).
def _channels(m: dict) -> int:
    """The convolution's channels: x, and B and C of every group."""
    return m["mamba_d_ssm"] + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def layer_params(m: dict) -> int:
    """Matmul parameters of one layer: q, k, v, o; the mixer's
    in-projection (z, [x, B, C], dt) and out-projection; the MLP. The
    convolution, dt_bias, A_log, D and the norms are below a thousandth
    and left out."""
    d, hd = m["hidden_size"], m["head_dim"]
    attn = 2 * d * m["num_attention_heads"] * hd \
        + 2 * d * m["num_key_value_heads"] * hd
    ssm = d * (m["mamba_d_ssm"] + _channels(m) + m["mamba_n_heads"]) \
        + m["mamba_d_ssm"] * d
    return attn + ssm + 3 * d * m["intermediate_size"]


def stored_param_bytes(m: dict, param_bytes: float) -> float:
    """Bytes of the weights as stored on the device: every layer, both
    tables."""
    n = m["num_hidden_layers"] * layer_params(m) \
        + 2 * m["vocab_size"] * m["hidden_size"]
    return n * param_bytes


def _state_bytes(m: dict) -> float:
    """One layer's float32 recurrent state of one slot."""
    return 4.0 * m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"]


def _tail_bytes(m: dict) -> float:
    """One layer's float32 convolution's tail of one slot."""
    return 4.0 * (m["mamba_d_conv"] - 1) * _channels(m)


def _row_bytes(m: dict, act_bytes: float) -> float:
    """What the recurrence reads and writes of one token in one layer
    beside its state: x in and y out, B and C in, dt in float32."""
    return 2.0 * m["mamba_d_ssm"] * act_bytes \
        + 2.0 * m["mamba_n_groups"] * m["mamba_d_state"] * act_bytes \
        + 4.0 * m["mamba_n_heads"]


def ssd_scan_flops(m: dict, tokens: float) -> float:
    """Every layer's recurrence over `tokens` tokens, counted from the
    recurrence whatever form computes it: a head a token, P x N
    multiply-adds into the state and P x N out of it (4 P N FLOPs)."""
    return 4.0 * m["mamba_d_head"] * m["mamba_d_state"] \
        * m["mamba_n_heads"] * tokens * m["num_hidden_layers"]


def ssd_scan_bytes(m: dict, tokens: float, act_bytes: float) -> float:
    """The least every layer's recurrence moves for one tile of `tokens`:
    each token's x, B, C and dt in and y out, the float32 state in and
    out once."""
    return m["num_hidden_layers"] * (tokens * _row_bytes(m, act_bytes)
                                     + 2.0 * _state_bytes(m))


def ssd_step_bytes(m: dict, rows: float, act_bytes: float) -> float:
    """The least every layer's one-row step moves for `rows` slots: each
    slot's float32 state in and out, and the row's x, B, C, dt and y."""
    return m["num_hidden_layers"] * rows * (
        2.0 * _state_bytes(m) + _row_bytes(m, act_bytes))


def causal_attention_flops(m: dict, batch: int, length: int,
                           backward: bool) -> float:
    """The one-shot path over a sequence: each layer's causal attention
    (QK^T and AV over the pairs at or below the diagonal) and its
    recurrence; the backward twice the forward."""
    pairs = length * (length + 1) / 2.0
    fwd = batch * (m["num_hidden_layers"] * 4.0 * pairs
                   * m["num_attention_heads"] * m["head_dim"]
                   + ssd_scan_flops(m, length))
    return fwd * (3.0 if backward else 1.0)


def train_step_flops(m: dict, batch: int, length: int) -> float:
    """Useful forward + backward FLOPs of one training step: 6 a matmul
    parameter a token, plus the mixers. (No cell trains this model: the
    scan has no tested backward, ROADMAP Reach B.)"""
    n = m["num_hidden_layers"] * layer_params(m) \
        + m["hidden_size"] * m["vocab_size"]
    return 6.0 * n * batch * length \
        + causal_attention_flops(m, batch, length, backward=True)


def decode_step_bytes(m: dict, live_lens: Iterable[float],
                      param_bytes: float, kv_bytes: float) -> float:
    """The LEAST one decode step must move: the weights as stored (only
    the unembedding half of the tables), each live slot's float32 state
    and convolution's tail of each layer in and out, and its live K and V.
    `live_lens` is a length a live slot (the reader
    metrics/decode_roofline_share.tok.py hands each request's)."""
    lens = [float(n) for n in live_lens]
    w = stored_param_bytes(m, param_bytes) \
        - m["vocab_size"] * m["hidden_size"] * param_bytes
    kv_row = 2.0 * m["num_key_value_heads"] * m["head_dim"] * kv_bytes
    return w + m["num_hidden_layers"] * (
        2.0 * (_state_bytes(m) + _tail_bytes(m)) * len(lens)
        + kv_row * sum(lens))
