"""The `phi4flash` family: Phi-4-mini-flash-reasoning's published keys
("SambaY", arXiv:2507.06607) mapped to the program's `TransformerLM` with
five kinds of layer (models/transformer.py, models/diff_attention.py,
models/ssm.py): "s6" (a Mamba-1 mixer, a float32 state a channel and a
convolution's tail), "win" and "att" (differential attention over a
window's ring, and over ONE cache by position), "xat" (differential
attention that projects a query alone and reads that one cache) and "gmu"
(a gated memory unit that reads the last "s6" layer's output). The layers
behind the one cache keep nothing, so only the rows a step samples pass
them.

What a family states is listed in families/mistral.py; this family's plain
reference is families/phi4flash_reference.py, its controls
families/phi4flash_controls.py. Its comparison with the reference has the
`falcon_h1` family's five numbers a case (`scored`, folded into the
harness's one share by `folded`): each served token's gap below its
position's largest reference logit; `logit_rms`, the program's own logits,
teacher-forced on the served tokens through the program's own one-slot
`SlotPool` (`program_rows`: tiles, then rows, the rows a tile names alone
passing the cacheless layers, as in the engine), against the reference's;
`edge_rms`, the same at the three rows that open every prefill tile after
the first (whose convolutions read the tile before's tails); `state_rel`
and `tail_rel`, the FIRST "s6" layer's state and tail in that pool against
the reference's, right after `insert` and after the last scored token, and
`state_rel_last` and `tail_rel_last`, the LAST one's (because the units
read its output), each under a limit of its own; and a sixth, `first_rms`,
the logits' deviation at the first scored row alone (the one row of the
comparison that comes out of a tile's program).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Iterable

from perfbench.families import phi4flash_reference as reference
from perfbench.families.falcon_h1 import logit_deviation, tail_deviation
from perfbench.families.phi4flash_reference import batch_loss  # noqa: F401
from perfbench.spec import ROOT, SpecError

KEY_BLOCK = 512      # the attention walks scratch, pool and ring in such


# ------------------------------------------------ configuration -> program
def model_kwargs(cfg: dict) -> dict:
    """The configuration file as keyword arguments of TransformerConfig.
    Refuses what the program cannot state, or states otherwise."""
    def refuse(ok, why):
        if not ok:
            raise SpecError(why)
    refuse(os.path.isfile(os.path.join(ROOT, "ray_tpu", "models",
                                       "diff_attention.py")),
           "this checkout's program states no differential attention, "
           "Mamba-1 layer or gated memory unit "
           "(ray_tpu/models/diff_attention.py): it cannot run the family")
    refuse(cfg.get("hidden_act", "silu") == "silu",
           "the program's MLP is SwiGLU")
    refuse(not cfg.get("mlp_bias") and not cfg.get("lm_head_bias")
           and cfg["tie_word_embeddings"],
           "the program's MLP and head have no bias, and this family's "
           "table is its head")
    refuse(not cfg.get("embd_pdrop") and not cfg.get("resid_pdrop"),
           "the program has no dropout")
    refuse(cfg["mb_per_layer"] == 2 and cfg["num_hidden_layers"] % 4 == 0,
           "the arrangement is written for mb_per_layer 2 and whole groups "
           "of four layers")
    a = cfg["assumed_sizes"]
    engine = cfg.get("engine") or {}
    refuse(engine.get("max_len", 0) <= cfg["max_position_embeddings"],
           "the engine's slots pass max_position_embeddings")
    budget = engine.get("prefill_budget", 0)
    refuse((engine.get("max_len", 0) + budget)
           % min(KEY_BLOCK, budget or 1) == 0
           and a["win_ring"] % min(KEY_BLOCK, cfg["sliding_window"]) == 0
           and a["win_ring"] >= cfg["sliding_window"] + budget,
           f"a slot and the largest tile together, and the ring, hold "
           f"whole blocks of {KEY_BLOCK} keys, the ring the window and the "
           f"tile")
    refuse(engine.get("prefix_cache_slots", 0) == 0
           and not engine.get("spec"),
           "prefix blocks hold K and V only, not a state, a tail or a ring "
           "(inference/kv_cache.py BlockStore): prefix_cache_slots must "
           "be 0")
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        norm_eps=cfg["layer_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        mixer_kinds=reference.kinds(cfg["num_hidden_layers"],
                                    cfg["mb_per_layer"]),
        window=cfg["sliding_window"], win_ring=a["win_ring"],
        s6_inner=a["expand"] * cfg["hidden_size"], s6_state=a["d_state"],
        s6_conv=a["d_conv"], s6_dt_rank=a["dt_rank"],
        diff_attn=True, layer_norm=True, attn_rope=False,
        scan_layers=False, dtype="bfloat16",
        param_dtype=cfg.get("param_dtype", "bfloat16"))


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
    prefill tile into a scratch that names the rows it will read (their
    logits alone come back: the cacheless layers and the head run over
    those rows and no other), and one decode row against the pools. (A
    control that plants a fault in a function these trace clears this
    cache: families/phi4flash_controls.py.)"""
    import jax

    def tile(params, toks, cache, rows):
        return model.apply({"params": params}, toks, cache=cache,
                           chunked_prefill=True, logit_rows=rows)

    def row(params, toks, cache):
        return model.apply({"params": params}, toks, cache=cache)

    return jax.jit(tile), jax.jit(row)


EDGE = 3             # rows at a tile's start that read the tile before's tail


def program_rows(params, m: dict, prompt, generated, model=None):
    """What the PROGRAM computes for one case, teacher-forced on the served
    tokens through its own one-slot `SlotPool`: the prompt prefilled in
    tiles of the engine's budget into a scratch (the tiles hand states,
    tails and rings on; the last tile's tail is rows no request owns; each
    tile names its first `EDGE` rows and its last real row, which alone
    pass the cacheless layers), the scratch made the pool's one slot, then
    one decode row a served token. -> {"rows": its logits [len(generated),
    vocab], float32, at the scored positions; "edge", "edge_rows": the
    positions of the first `EDGE` rows of every tile after the first and
    its logits there; "states" [2, layers, N, I] and "tails" [2, layers,
    K - 1, I]: the pool's, right after `insert` and after the last scored
    token}."""
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
            "tails": jnp.stack([c for _, c in held])}


def state_deviation(state, ref_state):
    """[2 (after insert, after the last token)]: the distance of a layer's
    state in the pool ([2, N, I]) from the reference's ([2, I, N]) as a
    share of the reference's norm, over the channels whose memory is
    longest and shortest alike (Frobenius)."""
    import jax.numpy as jnp
    import numpy as np
    want = jnp.swapaxes(ref_state, -1, -2)
    return np.asarray(jnp.sqrt(
        jnp.sum(jnp.square(state - want), (-2, -1))
        / jnp.sum(jnp.square(want), (-2, -1))), np.float64)


def scored(params, m: dict, prompt, generated, pad_to=None, program=None):
    """One case's numbers: `gaps`, `spread`, `logit_rms` and
    `logit_rms_each`, `first_rms` (the deviation at the FIRST scored
    position alone, the one row of the comparison that a tile's program
    makes: the prompt's last real row, named to the cacheless layers),
    `edge_rms` (0.0 for a prompt of one tile), `state_rel` and `tail_rel`
    (the FIRST "s6" layer's, which reads the embedding alone: bf16's
    rounding of that layer's own inputs and nothing upstream) and
    `state_rel_last` and `tail_rel_last` (the LAST one's, behind sixteen
    layers of bf16 activations: some thirty times the first's in the sound
    program, so each has a limit of its own), each the larger of right
    after `insert` and after the last scored token."""
    got = program or program_rows(params, m, prompt, generated)
    ref = reference.teacher_forced_gaps(params, m, prompt, generated,
                                        pad_to=pad_to, with_rows=True,
                                        also=got["edge"])
    dev, each = logit_deviation(got["rows"], ref["rows"])
    edge = logit_deviation(got["edge_rows"], ref["also"])[0] \
        if got["edge"] else 0.0
    by_layer = [float(max(state_deviation(got["states"][:, j],
                                          ref["states"][j])))
                for j in (0, -1)]
    tails = [float(max(tail_deviation(got["tails"][:, j], ref["tails"][j])))
             for j in (0, -1)]
    return {"gaps": ref["gaps"], "spread": ref["spread"], "logit_rms": dev,
            "logit_rms_each": each, "first_rms": each[0], "edge_rms": edge,
            "state_rel": by_layer[0], "state_rel_last": by_layer[1],
            "state_rel_by_layer": by_layer,
            "tail_rel": tails[0], "tail_rel_last": tails[1],
            "tail_rel_by_layer": tails}


def state_number(state_rel) -> float:
    """A case's first-layer state deviation as one number (it is one
    already: the controls' `judge` is shared with a family whose is a
    list)."""
    return float(state_rel)


def folded(score: dict, tol: dict):
    """The case's numbers as the harness's one: where its logit deviation
    (the median over the scored positions, at the first of them, or at the
    rows that open a tile) passes
    `logit_rms`, or a state's or a tail's deviation its limit (`state_rel`,
    `state_rel_last`, `tail_rel`, `tail_rel_last`), every token of the
    case counts as beyond the gap, at `logit_gap` x reading / limit."""
    over = max(score["logit_rms"] / tol["logit_rms"],
               score["first_rms"] / tol["logit_rms"],
               score["edge_rms"] / tol["logit_rms"],
               *(score[k] / tol[k] for k in (
                   "state_rel", "state_rel_last", "tail_rel",
                   "tail_rel_last")))
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
# Drawn so that every kind of layer reaches the logits. The table is the
# head (tied): logits = E . LayerNorm(h), so its rows are LOGITS /
# sqrt(hidden) an element for logits of deviation LOGITS; each of the 64
# residual branches adds about BRANCH an element, ten times a row of the
# table, so that a token's own embedding is about a hundredth of the last
# hidden state's energy and its own logit is lifted by 0.7, inside the
# spread (at equal size the model would repeat its input for ever: the
# row's product with itself over the hidden state's deviation is 7.5).
# - q and k so that a map's scores have deviation ATTN_LOGITS = 1, not the
#   other families' 3: 32 random layers amplify a perturbation by what each
#   layer's gain is, bf16's rounding of the activations among them, and a
#   softmax's gain is its scores' deviation. The sound program's logits
#   stood 0.16-0.28 from the float32 reference's at 3 (45-102 of a case's
#   256 tokens beyond the gap), 0.09-0.19 at 2, 0.05-0.08 at 1 (2-10 of
#   256), and every planted fault stands further from the sound program at
#   1 than at 2 (my chip runs, PR 53: PERF.md section 6). At 1 a head's
#   weight lies on about 1,800 of 5,000 positions, a pair's two maps are
#   correlated 0.37 and not alike, and lambda at its init alone moves the
#   logits by 0.6-0.7; v at 1; the biases 0.1; o undoes the pair norm's
#   (1 - l0) of ITS layer (0.64 at layer 1, 0.20 from layer 17 on);
# - the four lambda vectors N(0, 0.1^2) as published: lambda = l0 +- 0.11;
# - in_x, in_z at 1; the taps 1 / sqrt(4), their bias 0.1; x_proj at 1.5
#   (its input is silu of a unit normal, 0.6); dt_proj 1 / sqrt(dt_rank)
#   and its bias N(0, DT_BIAS_STD^2); `A_log` ~ N(0, A_LOG_STD^2): a spread
#   of dt x A over a layer's 5120 x 16 state elements from forgetting
#   within a token to remembering a few thousand (a numpy run of the
#   recurrence on these draws: 1.7 tokens at the median, 48 at the ninth
#   decile, 1,100 at the 99th per cent; M of root mean square 6.5, M *
#   silu(z) 3.8, which S6_GATED undoes in the out-projections);
# - logits of deviation LOGITS.
LOGITS, BRANCH, ATTN_LOGITS = 1.2, 0.25, 1.0
A_LOG_STD, DT_BIAS_STD, S6_GATED = 2.0, 2.0, 3.8


def weight_rule(names, shape):
    """A leaf's draw: None for ones (the norms' scales, the pair norm's,
    D), else (standard deviation, False: no leaf of this tree is a
    stack); an unknown leaf raises."""
    from ray_tpu.models.diff_attention import lambda_init
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    of = names[-2] if len(names) > 1 else ""
    if leaf in ("scale", "subln", "D"):
        return None
    if leaf == "embed":                     # [vocab, hidden]: tied
        return LOGITS / math.sqrt(shape[1]), False
    if leaf == "bias":
        return {"attn_norm": 0.02, "mlp_norm": 0.02, "final_norm": 0.02,
                "q": 0.1, "k": 0.1, "v": 0.1, "o": 0.1 * BRANCH,
                "dt_proj": DT_BIAS_STD}[of], False
    if leaf.startswith("lambda_"):
        return 0.1, False
    if leaf == "A_log":
        return A_LOG_STD, False
    if leaf == "conv_w":                    # [taps, channels]
        return 1.0 / math.sqrt(shape[0]), False
    if leaf == "conv_b":
        return 0.1, False
    if leaf == "o":                         # [pairs, 2 d, hidden]
        depth = int(names[0].split("_")[1])
        return BRANCH / ((1.0 - lambda_init(depth))
                         * math.sqrt(shape[0] * shape[1])), False
    gain = {"q": math.sqrt(ATTN_LOGITS), "k": math.sqrt(ATTN_LOGITS),
            "v": 1.0, "in_x": 1.0, "in_z": 1.0, "x_proj": 1.5,
            "dt_proj": 1.0, "in": 1.0,
            "out": BRANCH / S6_GATED,       # an "s6" layer's and a unit's
            "gate": 1.0, "up": 1.0,
            # silu(g) * u of two unit normals is 0.45 an element
            "down": BRANCH / 0.45}[leaf]
    return gain / math.sqrt(shape[0]), False            # [fan_in, ...]


# ------------------------------------------------------------- the counts
# `m` below is the configuration file's dict (the model's published keys
# and `assumed_sizes`).
def _sizes(m: dict):
    a = m["assumed_sizes"]
    return (m["hidden_size"], a["expand"] * m["hidden_size"], a["d_state"],
            a["d_conv"], a["dt_rank"])


def _layers(m: dict) -> dict:
    kinds = reference.kinds(m["num_hidden_layers"], m["mb_per_layer"])
    return {k: kinds.count(k) for k in ("s6", "win", "att", "xat", "gmu")}


def kv_row_bytes(m: dict, kv_bytes: float) -> float:
    """K and V of one position of one layer that keeps them."""
    head = m["hidden_size"] // m["num_attention_heads"]
    return 2.0 * head * m["num_key_value_heads"] * kv_bytes


def param_count(m: dict) -> int:
    """Every parameter: the table (tied: once), the MLPs, the mixers by
    kind, the norms."""
    d, I, N, K, R = _sizes(m)
    n = _layers(m)
    kv = d // m["num_attention_heads"] * m["num_key_value_heads"]
    hd = d // m["num_attention_heads"]
    lam = 4 * hd + 2 * hd
    s6 = d * 2 * I + K * I + I + I * (R + 2 * N) + R * I + I + N * I + I \
        + I * d
    attn = d * (d + 2 * kv) + (d + 2 * kv) + d * d + d + lam
    cross = d * d + d + d * d + d + lam
    unit = 2 * d * I
    mlp = 3 * d * m["intermediate_size"]
    norms = (2 * m["num_hidden_layers"] + 1) * 2 * d
    return m["vocab_size"] * d + m["num_hidden_layers"] * mlp \
        + n["s6"] * s6 + (n["win"] + n["att"]) * attn + n["xat"] * cross \
        + n["gmu"] * unit + norms


def stored_param_bytes(m: dict, param_bytes: float) -> float:
    """Bytes of the weights as stored on the device."""
    return param_count(m) * param_bytes


def _state_bytes(m: dict) -> float:
    """One "s6" layer's float32 state and convolution's tail of one
    slot."""
    _, I, N, K, _ = _sizes(m)
    return 4.0 * I * (N + K - 1)


def s6_scan_flops(m: dict, tokens: float) -> float:
    """Every "s6" layer's recurrence over `tokens` rows, counted from the
    recurrence whatever form computes it: 7 operations an element of the
    state a row (the decay's product and exponential, the input's two
    products, the state's multiply-add, the output's multiply-add)."""
    _, I, N, _, _ = _sizes(m)
    return _layers(m)["s6"] * tokens * I * N * 7.0


def s6_scan_bytes(m: dict, tokens: float, act_bytes: float) -> float:
    """The least every "s6" layer's recurrence moves for one tile of
    `tokens` rows: a row's x and z in and M out in the activations' type,
    its dt in float32, its B and C; the state and the tail in and out once
    a tile."""
    _, I, N, _, _ = _sizes(m)
    row = I * (3.0 * act_bytes + 4.0) + 2.0 * N * act_bytes
    return _layers(m)["s6"] * (tokens * row + 2.0 * _state_bytes(m))


def s6_step_bytes(m: dict, rows: float) -> float:
    """The least every "s6" layer's one-row step moves for `rows` live
    slots: each slot's float32 state and tail in and out."""
    return _layers(m)["s6"] * rows * 2.0 * _state_bytes(m)


def window_pairs(m: dict, pos0: float, rows: float) -> float:
    """(query, key) pairs of `rows` consecutive rows from position `pos0`
    under the window: row p attends min(p + 1, window) positions."""
    W = m["sliding_window"]
    full = lambda n: n * (n + 1) / 2.0 if n <= W \
        else W * (W + 1) / 2.0 + (n - W) * W              # noqa: E731
    return full(pos0 + rows) - full(pos0)


def diff_attend_flops(m: dict, pairs: float) -> float:
    """The differential attention over `pairs` (query, key) pairs of one
    layer: each of the query heads one score product of head_dim and one
    value product of 2 head_dim."""
    hd = m["hidden_size"] // m["num_attention_heads"]
    return pairs * m["num_attention_heads"] * 2.0 * (hd + 2 * hd)


def diff_attend_bytes(m: dict, positions: float, kv_bytes: float) -> float:
    """K and V of `positions` attended positions, read once."""
    return positions * kv_row_bytes(m, kv_bytes)


def diff_row_bytes(m: dict, live: float, live_window: float,
                   kv_bytes: float) -> float:
    """What the decode rows' attention must read: the live slots' `live`
    positions of the ONE cache by position once for each layer that reads
    it, and their `live_window` positions inside the window once for each
    window layer."""
    n = _layers(m)
    return (live * (n["att"] + n["xat"]) + live_window * n["win"]) \
        * kv_row_bytes(m, kv_bytes)


def causal_attention_flops(m: dict, batch: int, length: int,
                           backward: bool) -> float:
    """The one-shot path over a sequence: each attention layer's pairs
    (a window layer's inside its window) and the recurrences; the backward
    twice the forward."""
    n = _layers(m)
    pairs = (n["att"] + n["xat"]) * length * (length + 1) / 2.0 \
        + n["win"] * window_pairs(m, 0, length)
    fwd = batch * (diff_attend_flops(m, pairs) + s6_scan_flops(m, length))
    return fwd * (3.0 if backward else 1.0)


def train_step_flops(m: dict, batch: int, length: int) -> float:
    """Useful forward + backward FLOPs of one training step: 6 a matmul
    parameter a token, plus the mixers. (No cell trains this model: the
    scans have no tested backward, ROADMAP Reach B.)"""
    return 6.0 * param_count(m) * batch * length \
        + causal_attention_flops(m, batch, length, backward=True)


def decode_step_bytes(m: dict, live_lens: Iterable[float],
                      param_bytes: float, kv_bytes: float) -> float:
    """The LEAST one decode step must move: the weights as stored, once
    (the table as the head), each live slot's positions of the ONE cache
    by position once for each of the layers that read it, its window's
    positions once for each window layer, and its "s6" layers' float32
    states and tails in and out. `live_lens` is a length a live slot."""
    lens = [float(n) for n in live_lens]
    W = m["sliding_window"]
    return stored_param_bytes(m, param_bytes) \
        + diff_row_bytes(m, sum(lens), sum(min(n, W) for n in lens),
                         kv_bytes) \
        + s6_step_bytes(m, len(lens))
