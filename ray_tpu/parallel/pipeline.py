"""Pipeline parallelism: GPipe microbatch schedule over the `stage` mesh axis.

The reference expresses pipeline parallelism only through compiled DAGs —
multi-actor pipelines wired with NCCL P2P channels and a static execution
schedule (reference: python/ray/dag/compiled_dag_node.py:549,
experimental/channel/torch_tensor_nccl_channel.py, schedule in
dag/dag_node_operation.py). The TPU-native equivalent keeps the whole
pipeline inside ONE jitted SPMD program: stage weights are sharded over the
`stage` mesh axis, activations hop stage→stage via `lax.ppermute` (ICI
neighbor transfers), and the GPipe tick loop is a `lax.scan`. XLA overlaps
the ppermute with the next tick's compute; there are no per-hop host round
trips to hide, which is precisely why the µs-scale channel machinery of the
reference is unnecessary here.

Schedule (S stages, M microbatches, T = M + S - 1 ticks):

    tick t: stage s computes microbatch (t - s) if 0 <= t - s < M
            then shifts its output to stage s+1

Bubble fraction = (S-1)/T, the classic GPipe overhead; amortize with M >> S.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.mesh import AXIS_STAGE


def stack_stage_params(per_stage_params: list):
    """Stack a list of per-stage param pytrees into one tree with a leading
    stage dim (shard it over `stage` with stage_param_specs)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def stage_param_specs(stacked_params, stage_axis: str = AXIS_STAGE):
    """PartitionSpecs sharding the leading (stage) dim of every leaf."""
    return jax.tree.map(
        lambda a: P(stage_axis, *([None] * (a.ndim - 1))), stacked_params)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stacked_params,
                   microbatches: jax.Array,
                   mesh: Mesh,
                   stage_axis: str = AXIS_STAGE) -> jax.Array:
    """Run `stage_fn` as an S-stage GPipe pipeline.

    stage_fn(params_s, x) -> y must preserve the activation shape (the
    classic homogeneous-stage pipeline; embed/unembed live outside).

    stacked_params: pytree with leading dim S (see stack_stage_params),
        sharded over `stage_axis`.
    microbatches: [M, mb, ...] — M microbatches.
    Returns [M, mb, ...] outputs of the final stage.

    Differentiable: grads flow back through the ppermute chain (XLA emits
    the reverse permutes), so this composes with jax.grad/value_and_grad.
    """
    S = mesh.shape[stage_axis]
    M = microbatches.shape[0]
    T = M + S - 1

    p_specs = stage_param_specs(stacked_params, stage_axis)
    x_spec = P(*([None] * microbatches.ndim))

    def per_stage(params, xs):
        # params leaves arrive as [1, ...] (their stage shard); drop the dim
        params = jax.tree.map(lambda a: a[0], params)
        s = lax.axis_index(stage_axis)

        def tick(carry, t):
            prev_out = carry                       # activation shifted in
            mb_idx = jnp.clip(t, 0, M - 1)
            fresh = lax.dynamic_index_in_dim(xs, mb_idx, 0, keepdims=False)
            my_in = jnp.where(s == 0, fresh, prev_out)
            out = stage_fn(params, my_in)
            shifted = lax.ppermute(
                out, stage_axis, [(i, (i + 1) % S) for i in range(S)])
            return shifted, out

        _, outs = lax.scan(tick, jnp.zeros_like(xs[0]), jnp.arange(T))
        # outs[t] on stage s is microbatch (t - s): slice my M valid ticks
        mine = lax.dynamic_slice_in_dim(outs, s, M, axis=0)
        return mine[None]                          # [1, M, mb, ...]

    y = shard_map(per_stage, mesh=mesh,
                  in_specs=(p_specs, x_spec),
                  out_specs=P(stage_axis),
                  check_vma=False)(stacked_params, microbatches)
    # y: [S, M, mb, ...]; the final stage's row is the pipeline output
    return y[-1]


def make_pipeline_fns(stage_fn: Callable, mesh: Mesh,
                      stage_axis: str = AXIS_STAGE):
    """Convenience: returns apply(params, microbatches) closed over mesh."""
    def apply(stacked_params, microbatches):
        return pipeline_apply(stage_fn, stacked_params, microbatches,
                              mesh, stage_axis)
    return apply


# --------------------------------------------------------- MPMD schedules
# Host-level microbatch schedules for the MPMD pipeline (train/mpmd.py):
# per-stage programs on separate meshes, activations shipped stage-to-
# stage through the object store instead of lax.ppermute. Ops are
# ("F", mb) / ("B", mb) tuples in per-stage execution order — or
# ("F", mb, chunk) triples when the stage hosts interleaved virtual
# chunks (schedule_interleaved_1f1b); cross-stage data dependencies
# (F(vs, m) needs F(vs-1, m)'s activation, B(vs, m) needs B(vs+1, m)'s
# input-gradient, in VIRTUAL stage order vs = chunk*S + s) are enforced
# by the dispatcher, not the schedule — these lists only fix each
# stage's LOCAL order, which is what determines both the bubble and the
# grad-accumulation order (replay determinism depends on the latter).

OP_FWD = "F"
OP_BWD = "B"


def op_chunk(op) -> int:
    """Virtual-chunk index of a schedule op; plain (op, mb) tuples are
    chunk 0."""
    return op[2] if len(op) > 2 else 0


def _schedule_chunks(schedules) -> int:
    """Number of virtual chunks per stage in a schedule (v); 1 for the
    plain 2-tuple schedules."""
    v = 1
    for ops in schedules:
        for op in ops:
            v = max(v, op_chunk(op) + 1)
    return v


def schedule_gpipe(n_stages: int, n_microbatches: int):
    """GPipe (all-forward then all-backward) per-stage op lists. Peak
    live activations = n_microbatches on every stage."""
    if n_stages < 1 or n_microbatches < 1:
        raise ValueError("need n_stages >= 1 and n_microbatches >= 1")
    M = n_microbatches
    return [[(OP_FWD, m) for m in range(M)] + [(OP_BWD, m) for m in range(M)]
            for _ in range(n_stages)]


def schedule_1f1b(n_stages: int, n_microbatches: int):
    """Non-interleaved 1F1B (PipeDream-flush): stage s runs
    min(S-1-s, M) warmup forwards, then alternates one-forward/
    one-backward, then drains the remaining backwards. Same bubble as
    GPipe but peak live activations drop from M to min(S-s, M) — the
    schedule the MPMD trainer defaults to."""
    if n_stages < 1 or n_microbatches < 1:
        raise ValueError("need n_stages >= 1 and n_microbatches >= 1")
    S, M = n_stages, n_microbatches
    out = []
    for s in range(S):
        warmup = min(S - 1 - s, M)
        ops = [(OP_FWD, m) for m in range(warmup)]
        for i in range(M - warmup):
            ops.append((OP_FWD, warmup + i))
            ops.append((OP_BWD, i))
        for i in range(max(M - warmup, 0), M):
            ops.append((OP_BWD, i))
        out.append(ops)
    return out


def schedule_interleaved_1f1b(n_stages: int, n_microbatches: int, v: int):
    """Interleaved (virtual-stage) 1F1B, the arXiv 2412.14374 /
    Megatron-style schedule: each physical stage s hosts v virtual
    chunks, chunk c being virtual stage vs = c*S + s of a V = v*S deep
    virtual pipeline. Forwards fill in round-robin blocks of S
    microbatches per chunk, backwards drain the same way, so the flush
    bubble shrinks from (S-1)/(M+S-1) toward (S-1)/(v*M+S-1).

    Ops are (op, mb, chunk) triples, with each chunk's forwards AND
    backwards in strict microbatch order — the backward order is what
    makes grad accumulation, and therefore recovery replay, bit-
    identical to running the V virtual stages as V plain 1F1B stages.

    When M % S == 0 (Megatron's requirement) the closed-form ordering
    is used and the modeled bubble meets the analytic bound exactly:
    stage s runs 2*(S-1-s) + (v-1)*S warmup forwards, then 1F1B
    alternation, forwards/backwards drawn from chunks in round-robin
    blocks of S microbatches (backwards from the deepest chunk first).
    Otherwise a unit-time greedy simulation over the virtual-stage
    dependency DAG (F(vs, m) after F(vs-1, m); B(vs, m) after F(vs, m)
    and B(vs+1, m)) emits a valid — slightly bubblier — schedule;
    either way the result is deadlock-free (the closed form is
    validated by simulate_schedule, the greedy order is a projection
    of a global topological execution).
    """
    if n_stages < 1 or n_microbatches < 1 or v < 1:
        raise ValueError("need n_stages >= 1, n_microbatches >= 1, v >= 1")
    if v == 1:
        return [[(op, mb, 0) for op, mb in ops]
                for ops in schedule_1f1b(n_stages, n_microbatches)]
    if n_microbatches % n_stages == 0:
        return _interleaved_closed_form(n_stages, n_microbatches, v)
    return _interleaved_greedy(n_stages, n_microbatches, v)


def _interleaved_closed_form(S: int, M: int, v: int):
    """Megatron-style interleaved 1F1B for M % S == 0; bubble hits
    (S-1)/(v*M+S-1) under uniform op times."""
    total = v * M
    out = []
    for s in range(S):
        fseq, fptr = [], [0] * v
        for k in range(total):
            c = (k // S) % v
            fseq.append((OP_FWD, fptr[c], c))
            fptr[c] += 1
        bseq, bptr = [], [0] * v
        for k in range(total):
            c = v - 1 - (k // S) % v
            bseq.append((OP_BWD, bptr[c], c))
            bptr[c] += 1
        warmup = min(2 * (S - 1 - s) + (v - 1) * S, total)
        ops = list(fseq[:warmup])
        for i in range(total - warmup):
            ops.append(fseq[warmup + i])
            ops.append(bseq[i])
        ops.extend(bseq[max(total - warmup, 0):])
        out.append(ops)
    simulate_schedule(out)                 # assert deadlock-freedom
    return out


def _interleaved_greedy(S: int, M: int, v: int):
    """Greedy fallback for M % S != 0: backward-first unit-time
    simulation over the virtual-stage DAG; valid for any (S, M, v) but
    does not always reach the analytic bubble bound."""
    V = v * S
    next_f = [0] * V                     # per-virtual-stage microbatch FIFOs
    next_b = [0] * V
    f_done = [[-1] * M for _ in range(V)]   # finish tick, -1 = not yet
    b_done = [[-1] * M for _ in range(V)]
    out = [[] for _ in range(S)]
    remaining = 2 * V * M
    tick = 0
    while remaining:
        ran_this_tick = []
        for s in range(S):
            # Backward-first (1F1B steady state bounds live activations);
            # among ready ops prefer the one earliest in the interleaved
            # round-robin order: blocks of S microbatches per chunk,
            # deeper chunks drain first on the backward side.
            best = None
            for c in range(v):
                vs = c * S + s
                m = next_b[vs]
                if (m < M and 0 <= f_done[vs][m] < tick
                        and (vs == V - 1 or 0 <= b_done[vs + 1][m] < tick)):
                    key = (0, (m // S) * V + (V - 1 - vs))
                    if best is None or key < best[0]:
                        best = (key, OP_BWD, m, c, vs)
            if best is None:
                for c in range(v):
                    vs = c * S + s
                    m = next_f[vs]
                    if (m < M and
                            (vs == 0 or 0 <= f_done[vs - 1][m] < tick)):
                        key = (1, (m // S) * V + vs)
                        if best is None or key < best[0]:
                            best = (key, OP_FWD, m, c, vs)
            if best is not None:
                ran_this_tick.append(best)
        for _key, op, m, c, vs in ran_this_tick:
            if op == OP_FWD:
                next_f[vs] += 1
                f_done[vs][m] = tick
            else:
                next_b[vs] += 1
                b_done[vs][m] = tick
            out[vs % S].append((op, m, c))
            remaining -= 1
        if not ran_this_tick:          # unreachable for a DAG; guard anyway
            raise ValueError("interleaved schedule generator stalled at "
                             f"tick {tick} with {remaining} ops left")
        tick += 1
    return out


def make_schedule(kind: str, n_stages: int, n_microbatches: int,
                  virtual: int = 1):
    if virtual < 1:
        raise ValueError("virtual stage count must be >= 1")
    if kind == "1f1b":
        if virtual > 1:
            return schedule_interleaved_1f1b(
                n_stages, n_microbatches, virtual)
        return schedule_1f1b(n_stages, n_microbatches)
    if kind == "gpipe":
        if virtual > 1:
            raise ValueError(
                "interleaved virtual stages require the '1f1b' schedule")
        return schedule_gpipe(n_stages, n_microbatches)
    raise ValueError(f"unknown pipeline schedule {kind!r} "
                     "(expected '1f1b' or 'gpipe')")


def peak_live_activations(stage_ops, grad_buffers: bool = True) -> int:
    """Buffer high-water mark of one stage's op list, in microbatch-
    sized units: forwards outstanding (saved inputs awaiting their
    backward) plus — once a chunk's first backward has run — that
    chunk's grad-accumulation buffer, which stays live from first
    backward until the step-boundary apply. The grad buffers are what
    the old activation-only count missed: in 1F1B steady state a stage
    holds min(S-s, M) stashes AND its running grad sum, so the true
    peak is min(S-s, M) + 1. Pass grad_buffers=False for the legacy
    activation-only number."""
    live = peak = 0
    accumulating: set = set()
    for op in stage_ops:
        if op[0] == OP_FWD:
            live += 1
        else:
            live -= 1
            accumulating.add(op_chunk(op))
        held = live + (len(accumulating) if grad_buffers else 0)
        peak = max(peak, held)
    return peak


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int,
                             virtual: int = 1) -> float:
    """Analytic flush-bubble fraction: (S-1)/(M+S-1) for GPipe and
    plain 1F1B, shrinking to (S-1)/(v*M+S-1) under v-way interleaving
    (each stage's idle gaps are filled by the other chunks' work); the
    probe reports the measured per-stage idle fraction next to both
    bounds."""
    return (n_stages - 1) / (virtual * n_microbatches + n_stages - 1)


def simulate_schedule(schedules):
    """Dependency-order simulation of per-stage op lists: repeatedly
    sweep the stages, running each stage's next op when its cross-stage
    input is available. Handles both plain (op, mb) and interleaved
    (op, mb, chunk) schedules — dependencies run in VIRTUAL stage order
    vs = chunk*S + s. Returns the global execution order as
    (sweep, stage, op, mb, chunk) tuples; raises if the schedule
    deadlocks (an op whose dependency can never arrive). The MPMD
    dispatcher uses the same sweep against live stage handles; tests
    use this pure version to pin schedule correctness, and recovery
    replay inherits its determinism from the same per-stage order."""
    S = len(schedules)
    V = S * _schedule_chunks(schedules)
    queues = [list(ops) for ops in schedules]
    fwd_done = [set() for _ in range(V)]   # mb whose F(vs, m) completed
    bwd_done = [set() for _ in range(V)]
    order = []
    tick = 0
    while any(queues):
        progressed = False
        for s in range(S):
            while queues[s]:
                op = queues[s][0]
                kind, mb, chunk = op[0], op[1], op_chunk(op)
                vs = chunk * S + s
                if kind == OP_FWD:
                    ready = vs == 0 or mb in fwd_done[vs - 1]
                else:
                    ready = (mb in fwd_done[vs]
                             and (vs == V - 1 or mb in bwd_done[vs + 1]))
                if not ready:
                    break
                queues[s].pop(0)
                (fwd_done if kind == OP_FWD else bwd_done)[vs].add(mb)
                order.append((tick, s, kind, mb, chunk))
                progressed = True
        if not progressed:
            raise ValueError(
                "pipeline schedule deadlocked; remaining per-stage ops: "
                f"{[q[:2] for q in queues]}")
        tick += 1
    return order


def simulate_timeline(schedules, op_time, transfer_time: float = 0.0):
    """Event-timeline model of a schedule's parallel execution: each
    stage executes its op list in order, an op starting at
    max(stage free, dependencies finished + transfer_time) and running
    for op_time(stage, op_kind, chunk) seconds. This is the physics the
    bubble bounds approximate — the probe feeds it MEASURED per-op
    durations to model the parallel step time and per-stage idle
    fraction on hosts that can't run S real processes side by side.

    Returns {"span": makespan, "stage_busy": [...], "stage_idle_frac":
    [...], "bubble_fraction": mean idle frac} (idle measured against
    the full makespan, matching how the trainer's per-stage
    bubble_fraction gauge is computed)."""
    S = len(schedules)
    order = simulate_schedule(schedules)   # also validates deadlock-freedom
    finish: dict = {}                      # (kind, mb, vs) -> finish time
    stage_free = [0.0] * S
    stage_busy = [0.0] * S
    for _tick, s, kind, mb, chunk in order:
        vs = chunk * S + s
        deps = []
        if kind == OP_FWD:
            if vs > 0:
                deps.append(finish[(OP_FWD, mb, vs - 1)] + transfer_time)
        else:
            deps.append(finish[(OP_FWD, mb, vs)])
            V = S * _schedule_chunks(schedules)
            if vs < V - 1:
                deps.append(finish[(OP_BWD, mb, vs + 1)] + transfer_time)
        start = max([stage_free[s]] + deps)
        dur = float(op_time(s, kind, chunk))
        finish[(kind, mb, vs)] = start + dur
        stage_free[s] = start + dur
        stage_busy[s] += dur
    span = max(stage_free) if S else 0.0
    idle = [1.0 - busy / span if span > 0 else 0.0 for busy in stage_busy]
    return {
        "span": span,
        "stage_busy": stage_busy,
        "stage_idle_frac": idle,
        "bubble_fraction": sum(idle) / S if S else 0.0,
    }
