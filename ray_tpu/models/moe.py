"""Mixture-of-Experts feed-forward with expert parallelism.

GShard/Switch-style dense dispatch, designed for the MXU and XLA SPMD:
routing builds one-hot dispatch/combine tensors and the token→expert
shuffle is an einsum — under an `expert`-sharded mesh axis XLA lowers it
to an all-to-all over ICI, with expert FFN weights stacked as one
[E, d, ff] tensor (logical axes ("experts", "embed", "mlp")) so every
expert's matmul runs at full tile size. No counterpart in the reference
(it orchestrates torch processes and ships no MoE, SURVEY §2.4: EP listed
as "absent — must be built natively").

Routing (a batch row is the dispatch group: a sequence in training, a
prefill tile, one decode row, or in the engine's step a tile with the
slots' decode rows behind it), by one of two routers (`cfg.router`):
- "softmax": a softmax in fp32 over ALL `n_experts`, top-k experts per
  token, gates renormalized;
- "sigmoid" (`sigmoid_route`): a sigmoid score an expert in fp32; the
  top-k of score + `router_bias` (a learned bias an expert that CHOOSES
  and does not weigh) are taken and weighed by their scores, renormalized
  where `route_norm`, times `route_scale`;
- per-expert capacity C = min(L, ceil(capacity_factor * L * k / E)).
  Training drops the tokens over capacity (standard Switch behavior, keeps
  shapes static); serving (`exact`: the cached forward) drops nothing: a
  pick past its expert's capacity is computed by the overflow route below;
- aux load-balancing loss (Switch eq. 4): E * Σ_e frac_tokens_e · mean_prob_e.

The experts are SwiGLUs of `expert_d_ff` (a width of their own beside the
dense MLP's `d_ff`, which a model's leading `n_dense_layers` keep:
`transformer.Block`). With `n_shared_experts` a further SwiGLU of that
many experts' width takes every row whatever it picked, added to the
routed experts' result; it is whole on every rank.

What the layer holds: all `n_experts`, or the contiguous range
`experts_held` = (first, count) of them — one rank's share of a layer that
several chips divide. The router, the top-k and the gates are the whole
layer's; the dispatch, the expert weights and the result are the held
experts' alone: what the absent experts would add to a token is left out
(no code stands in for the other ranks or their exchange), so the shares
of all ranks add up to the whole layer's result.

The cost of the expert matmuls, and which form runs where. The form is
chosen from the shapes alone, here (`takes_grouped`), between two:

- the DENSE DISPATCH (`_dispatched`): held-experts x groups x C rows,
  every held expert over C rows of every group. Training (`exact` False:
  the drops and the aux loss as they are); a group of one row or of a few
  (a decode step: 16 groups of L = 1, every expert is hit and the weights'
  stream is the floor); a layer sharded over a mesh; and C < L (many
  narrow experts: 128, top-8, 16 held would compute E / k = 16 times the
  rows really routed at C = L, and take a C about twice the expected
  load), where the serving forward adds the overflow route: the picks
  past an expert's capacity, rare, go through every held expert over the
  whole group under a `lax.cond` that runs only in a step where some pick
  overflowed.
- the GROUPED FORM (`_grouped`): the rows that were routed. Where the
  serving forward's C is the group's whole length (few wide experts,
  Mixtral's 8 with top-2, at capacity_factor = E / k) the dense dispatch
  computes E / k times the rows routed and no pick can overflow, so the
  picks are sorted by expert (`ops/grouped_matmul.py` `sort_picks`: a
  stable sort on (expert, tail, row, k)) and ONE grouped SwiGLU runs over
  them, each expert's weights read where they lie, and each row takes its
  k results back weighed by its gates (the gates rounded to the rows'
  type as the dense form rounds them, the sum in float32). It is taken
  where the sorted layout's static row bound k L + E (tile - 1) is under
  the dense form's E L (Mixtral's step of 272 rows: 544 + 8 x 127 = 1,560
  against 2,176 at a tile of 128). **Each expert's rows start on a
  boundary of a span of two tiles**: a span then belongs to one expert,
  whose weight blocks pass it once; a kernel that lets two experts share
  a block of rows visits it twice and streams the second's blocks again,
  and so does a span of one tile that an expert's load passes, and this
  step is bound by the weights' stream. What is computed follows the
  tile: a tile of a span that holds no row is skipped. On a TPU the rows
  go through the Pallas kernel where its `fits` says the widths tile
  (`_kernel_takes`), and through its XLA form everywhere else (the sort,
  the gather and the combine are then XLA's too). Under a scan over the
  kernel is handed EVERY layer's experts and the layer's number
  (`TransformerLM._stacked_experts`): a layer's own weights are a slice
  of the stacked parameters there, which XLA fuses into the dense form's
  einsums and copies whole, 2.8 GB a layer, for a custom call.

Rows no request owns (the padded tail of a prefill tile, an idle slot's
decode row; `real` False) are routed nowhere where that matters: their
picks are taken out before the capacity is counted, so they fill no
expert's capacity and are not counted.

The last `tail` rows of a group (the slots' decode rows behind a prefill
tile) take their places in an expert's capacity after EVERY pick of the
rows before them. So where a tile's pick sits in its expert's matmul and
whether it fits, and with them every bit of what the tile computes, are
the same whatever rides behind it (a row's result may depend on its place
in a matmul in a last bit: it does on the CPU backend).

Counters: where the caller makes the "counters" collection mutable
(`apply(..., mutable=["counters"])`, the engine for a layer that holds a
share of its experts or whose capacity is the whole group) the layer sows
int32[2]: the rows its expert matmuls computed (the grouped form's: each
held expert's picks rounded up to the tile) and the picks of real rows
that landed on a held expert; where `counts_hits` (routing in groups, a
share of the experts held) int32[4], behind them the real rows with at least
one pick on a held expert and the real rows. `rows_follow_routing` says for
which models the engine has something to read.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ray_tpu.models.transformer import _p
from ray_tpu.ops import grouped_matmul
from ray_tpu.parallel.mesh import current_mesh
from ray_tpu.parallel.sharding import constrain


def _kernel_takes(N: int, D: int, F: int, tile: int, dtype) -> bool:
    """Whether the grouped form's rows go through the Pallas kernel (a TPU
    backend and widths that tile) or through its XLA form."""
    return jax.default_backend() == "tpu" \
        and grouped_matmul.fits(N, D, F, tile, dtype)


def capacity(cfg, L: int) -> int:
    """The rows of a group of L an expert holds."""
    return min(L, max(1, math.ceil(
        cfg.capacity_factor * L * cfg.expert_top_k / cfg.n_experts)))


def takes_grouped(cfg, L: int, exact: bool = True) -> bool:
    """Whether the expert layer runs a group of L rows in the grouped form
    (the module's docstring): from `exact`, the shapes and the mesh."""
    E, K = cfg.n_experts, cfg.expert_top_k
    held = (cfg.experts_held or (0, E))[1]
    tile = grouped_matmul.row_tile(L, K, E)
    mesh = current_mesh()
    return (exact and capacity(cfg, L) == L
            and K * L + held * (tile - 1) < held * L
            and (mesh is None or mesh.size == 1))


def rows_follow_routing(cfg) -> bool:
    """Whether the rows the expert matmuls compute are more than the
    shapes say: a layer that holds a share of its experts, or one whose
    capacity is the whole group at every length (its longer groups run
    the grouped form)."""
    return cfg.n_experts > 0 and (
        bool(cfg.experts_held)
        or cfg.capacity_factor * cfg.expert_top_k >= cfg.n_experts)


def sigmoid_route(x, router, bias, k: int, n_group: int = 1,
                  topk_group: int = 1):
    """The sigmoid router: a score an expert in float32, the k experts of
    largest score + bias, weighed by their SCORES (the bias chooses and
    does not weigh). `n_group` > 1 (DeepSeek-V3's routing in groups): the
    experts lie in `n_group` groups of E / n_group neighbours; a group's
    score is the sum of its two largest score + bias; the `topk_group` best
    groups stay and the k experts are taken among theirs. -> (scores
    [B, L, E], the taken experts' scores and numbers [B, L, k])."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router)
    choose = scores + bias
    if n_group > 1:
        # (by maxima and ranks, not by sorting: three `top_k`s a layer over
        # a 1,056-row tile were a millisecond a layer on the chip, PR 58)
        by_group = choose.reshape(choose.shape[:-1] + (n_group, -1))
        first = by_group.max(-1, keepdims=True)
        at = jnp.argmax(by_group, axis=-1)[..., None]
        second = jnp.where(jnp.arange(by_group.shape[-1]) == at, -jnp.inf,
                           by_group).max(-1)
        best = first[..., 0] + second                            # [B, L, G]
        # a group stays where fewer than `topk_group` groups score above it
        # (a tie to the lower number, as `top_k` has it)
        g = jnp.arange(n_group)
        above = (best[..., None, :] > best[..., :, None]) | (
            (best[..., None, :] == best[..., :, None]) & (g < g[:, None]))
        stays = above.sum(-1) < topk_group
        choose = jnp.where(stays[..., None], by_group,
                           -jnp.inf).reshape(choose.shape)
    _, taken = jax.lax.top_k(choose, k)
    return scores, jnp.take_along_axis(scores, taken, axis=-1), taken


def counts_hits(cfg) -> bool:
    """Whether the layer's counters carry, beside the rows computed and the
    picks, the real rows with at least one pick on a held expert and the
    real rows: where the routing is in groups and the layer holds a share
    of the experts, so that how many rows reach this share at all is the
    grouping's doing."""
    return cfg.n_group > 1 and bool(cfg.experts_held)


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense MLP block (gate/up/down SwiGLU),
    with `cfg.n_experts` experts, top-`cfg.expert_top_k` routing by
    `cfg.router`, and `cfg.n_shared_experts` shared ones."""

    cfg: Any

    @nn.compact
    def __call__(self, x, real=None, exact: bool = False, tail: int = 0,
                 stack=None):
        """-> (out, aux loss). `real` [B, L] bool: the rows a request
        owns (None: all). `exact`: the serving forward, which drops no
        pick. `tail`: the group's last rows that are counted after the
        others (the module's docstring). `stack`: ((gate, up, down) of ALL
        the scanned layers, [n_layers, E, ..]; this layer's number), for
        the grouped form to read this layer's experts where they lie."""
        cfg = self.cfg
        B, L, D = x.shape
        E, K = cfg.n_experts, cfg.expert_top_k
        first, held = cfg.experts_held or (0, E)
        C = capacity(cfg, L)

        F = cfg.expert_d_ff or cfg.d_ff
        router = self.param(
            "router", _p(nn.initializers.lecun_normal(), "embed", "experts"),
            (D, E), jnp.float32)
        with jax.named_scope("moe_router"):
            if cfg.router == "sigmoid":
                bias = self.param("router_bias", _p(nn.initializers.zeros,
                                                    "experts"), (E,),
                                  jnp.float32)
                groups = (cfg.n_group, cfg.topk_group) \
                    if cfg.n_group > 1 else ()
                probs, gate_vals, gate_idx = sigmoid_route(
                    x, router, bias, K, *groups)
            else:
                probs = jax.nn.softmax(
                    x.astype(jnp.float32) @ router, axis=-1)   # [B,L,E]
                gate_vals, gate_idx = jax.lax.top_k(probs, K)  # [B,L,K]
            if cfg.route_norm:
                gate_vals = gate_vals / jnp.maximum(
                    gate_vals.sum(-1, keepdims=True), 1e-9)
            if cfg.route_scale != 1.0:
                gate_vals = cfg.route_scale * gate_vals

        sel_all = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [B,L,K,E]
        counting = self.is_mutable_collection("counters")
        if real is not None and (C < L or counting):
            sel_all = sel_all * real[:, :, None, None]
        # the held experts' columns
        sel = sel_all if held == E else sel_all[..., first:first + held]

        # one stacked array per projection
        w_gate = self.param(
            "gate", _p(nn.initializers.lecun_normal(),
                       "experts", "embed", "mlp"),
            (held, D, F), cfg.param_dtype)
        w_up = self.param(
            "up", _p(nn.initializers.lecun_normal(),
                     "experts", "embed", "mlp"),
            (held, D, F), cfg.param_dtype)
        w_down = self.param(
            "down", _p(nn.initializers.lecun_normal(),
                       "experts", "mlp", "embed"),
            (held, F, D), cfg.param_dtype)
        weights = w_gate, w_up, w_down
        if takes_grouped(cfg, L, exact):
            out, rows, picks = self._grouped(x, gate_vals, gate_idx, real,
                                             tail, weights, stack)
        else:
            out, rows = self._dispatched(x, gate_vals, sel, C, exact, tail,
                                         weights)
            picks = None
        if cfg.n_shared_experts:
            # the shared expert: every row passes it, whatever it picked
            # (and whatever this rank holds: it is whole on each)
            dense = lambda feats, axes, name: nn.DenseGeneral(  # noqa: E731
                feats, axis=-1, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name,
                kernel_init=_p(nn.initializers.lecun_normal(), *axes))
            with jax.named_scope("moe_shared"):
                wide = cfg.n_shared_experts * F
                y = nn.silu(dense(wide, ("embed", "mlp"), "shared_gate")(x)) \
                    * dense(wide, ("embed", "mlp"), "shared_up")(x)
                out = out + dense(D, ("mlp", "embed"), "shared_down")(y)
        if counting:
            # what `moe_rows_per_pick` divides
            if picks is None:
                picks = sel.sum().astype(jnp.int32)
            counts = [jnp.asarray(rows, jnp.int32), picks]
            if counts_hits(cfg):
                # (`sel` is zeroed where a row is not real: above)
                own = jnp.ones((B, L), bool) if real is None else real
                counts += [(sel.sum((2, 3)) > 0).sum().astype(jnp.int32),
                           own.sum().astype(jnp.int32)]
            self.sow("counters", "rows_and_picks", jnp.stack(counts))

        # Switch load-balance loss: encourages uniform routing
        frac_tokens = sel_all.sum((1, 2)) / (L * K)            # [B,E]
        mean_probs = probs.mean(1)                             # [B,E]
        aux = cfg.n_experts * (frac_tokens * mean_probs).sum(-1).mean()
        return out, aux

    def _dispatched(self, x, gate_vals, sel, C: int, exact: bool, tail: int,
                    weights):
        """The dense dispatch: every held expert over C rows a group ->
        (the routed experts' result, the rows the expert matmuls
        computed). `sel` [B, L, K, E]: the picks of the E held experts."""
        B, L, _ = x.shape
        K, E = sel.shape[2:]
        cfg = self.cfg
        w_gate, w_up, w_down = (w.astype(cfg.dtype) for w in weights)

        # expert-choice position: for the j-th routing slot, a token's slot
        # in expert e's buffer is the number of earlier (token, slot) picks
        # of e, counting slots in priority order (slot 0 of every token
        # first — standard top-k dispatch priority)
        def places(sel, taken=0.0):
            n = sel.shape[1]
            flat = sel.transpose(0, 2, 1, 3).reshape(B, K * n, E)
            before = jnp.cumsum(flat, axis=1) - flat + taken   # slot-major
            return before.reshape(B, K, n, E).transpose(0, 2, 1, 3)

        if tail:
            head = sel[:, :L - tail]
            pos = jnp.concatenate(
                [places(head), places(sel[:, L - tail:],
                                      head.sum((1, 2))[:, None])], axis=1)
        else:
            pos = places(sel)                                  # [B,L,K,E]
        pos = (pos * sel).sum(-1)                              # [B,L,K]
        keep = (pos < C).astype(gate_vals.dtype)

        # combine[b,l,e,c]: gate weight of token (b,l) at slot c of expert e
        onehot_c = jax.nn.one_hot(pos.astype(jnp.int32), C,
                                  dtype=jnp.float32)           # [B,L,K,C]
        combine = jnp.einsum("blk,blke,blkc->blec",
                             gate_vals * keep, sel, onehot_c)
        dispatch = (combine > 0).astype(x.dtype)

        # token→expert shuffle; sharding the e dim over the expert axis
        # turns this einsum into an all-to-all under SPMD
        expert_in = jnp.einsum("blec,bld->ebcd", dispatch, x)
        expert_in = constrain(expert_in, ("experts", None, None, "embed"))

        def experts(rows):                       # [E, .., D] -> [E, .., D]
            h = jnp.einsum("ebcd,edf->ebcf", rows, w_gate)
            u = jnp.einsum("ebcd,edf->ebcf", rows, w_up)
            y = nn.silu(h) * u
            return jnp.einsum("ebcf,efd->ebcd", y, w_down)

        with jax.named_scope("moe_experts"):
            expert_out = experts(expert_in)
        expert_out = constrain(expert_out,
                               ("experts", None, None, "embed"))

        out = jnp.einsum("blec,ebcd->bld",
                         combine.astype(x.dtype), expert_out)

        rows = E * B * C
        if exact and C < L:
            # the exact overflow route: a pick past its expert's capacity
            # is computed by running every held expert over the whole
            # group, weighted by the overflowed picks' gates alone
            spill = jnp.einsum("blk,blke->ble", gate_vals * (1.0 - keep),
                               sel)                            # [B,L,E]
            spilled = jnp.any(spill > 0)
            out = out + jax.lax.cond(
                spilled,
                lambda: jnp.einsum(
                    "ble,ebld->bld", spill.astype(x.dtype),
                    experts(jnp.broadcast_to(x, (E,) + x.shape))),
                lambda: jnp.zeros_like(out))
            rows = rows + spilled.astype(jnp.int32) * (E * B * L)
        return out, rows

    def _grouped(self, x, gate_vals, gate_idx, real, tail: int, weights,
                 stack=None):
        """The grouped form: the picks of real rows on a held expert,
        sorted by expert from span boundaries, through ONE grouped SwiGLU
        over the rows routed, and each row's K results back, weighed by its
        gates -> (the routed experts' result, the rows computed, the picks
        computed). With `stack` the kernel is handed every layer's experts
        as ONE stack [n_layers x E, ..] and the spans' table counts from
        this layer's first: under a scan a layer's own weights are a slice
        of the parameters, which XLA copies whole for a custom call."""
        cfg = self.cfg
        B, L, D = x.shape
        K, N = cfg.expert_top_k, B * L
        first, held = cfg.experts_held or (0, cfg.n_experts)
        tile = grouped_matmul.row_tile(L, K, cfg.n_experts)
        local = gate_idx - first
        valid = (local >= 0) & (local < held)
        if real is not None:
            valid &= real[:, :, None]
        late = jnp.tile(jnp.arange(L) >= L - tail, B) if tail else None
        span_expert, span_rows, *layout = grouped_matmul.sort_picks(
            local.reshape(N, K), valid.reshape(N, K), held,
            grouped_matmul.SPAN * tile, late)
        if stack is not None:
            weights, layer = stack
            weights = (w.reshape((-1,) + w.shape[2:]) for w in weights)
            span_expert = span_expert + layer * held
        w_gate, w_up, w_down = (w.astype(cfg.dtype) for w in weights)
        # the gates rounded to the rows' type as the dense form rounds them
        gates = (gate_vals * valid).astype(x.dtype).astype(jnp.float32)
        form = grouped_matmul.routed_swiglu if _kernel_takes(
            N, D, w_gate.shape[-1], tile, x.dtype) \
            else grouped_matmul.routed_swiglu_reference
        with jax.named_scope("moe_experts"):
            out = form(x.reshape(N, D), w_gate, w_up, w_down, span_expert,
                       span_rows, *layout, gates.reshape(N, K),
                       tile=tile).reshape(B, L, D)
        return (out, grouped_matmul.rows_computed(span_rows, tile),
                valid.sum().astype(jnp.int32))
