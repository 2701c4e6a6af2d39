"""Elastic MPMD pipeline training: per-stage programs on per-stage
meshes, activations over the data plane, stage-level preemption recovery.

The SPMD pipeline (parallel/pipeline.py) keeps every stage inside ONE
jitted program on one mesh — the right shape inside a slice, but it
cannot span slices (no ICI) and a single lost host kills the whole
program. This module is the cross-slice shape from the MPMD pipeline
paper (arXiv 2412.14374): each stage is its OWN program compiled once on
its OWN mesh/slice, hosted by an actor; activations and input-gradients
hop stage-to-stage as object-store objects — created in the pinned
shared-memory arena by the producing actor and, across nodes, shipped by
the PR 5 zero-copy binary data plane (the controller only routes refs,
bytes never visit it). The microbatch schedule (1F1B by default, GPipe
optional — parallel/pipeline.py schedule_*) is dispatched ref-chained:
every op of a step is submitted up front and the per-actor ordered
queues + object dependencies realize the pipeline without a host round
trip per hop.

Stage loss is a first-class lifecycle, mirroring PR 9's serving shape:

  notice   — each stage actor watches ``tpu.check_preemption_notice()``
             (plus its per-stage marker file, the chaos channel); a
             preempting stage is migrated at the NEXT step boundary:
             fresh shard checkpoint, replacement provisioned, old actor
             reaped — zero steps replayed.
  crash    — a stage actor that dies mid-step (preemption without
             notice, chaos ``StageKiller``) surfaces as failed applies /
             dead pings. Surviving stages PARK at a bounded-deadline
             barrier (abort the in-flight step, roll back to the last
             checkpoint boundary — their params never left the process);
             the controller re-provisions the stage from its shard
             checkpoint (object-store snapshot ref first; storage shard
             via ``sharded_checkpoint.restore_and_broadcast`` when a
             ``storage_path`` is configured and the ref is gone), then
             REPLAYS the buffered input microbatches. Replay re-runs the
             identical per-stage op order through the identical
             compiled-once programs, so post-replay optimizer state is
             bit-identical to an uninterrupted run; training resumes
             within ``replay_depth + 1`` steps of where it stopped.
  degrade  — a survivor that misses the park barrier
             (``mpmd_barrier_deadline_s``) or an exhausted
             ``FailureConfig.max_failures`` budget raises
             :class:`PipelineDegradedError`; the job-level
             ``restart_policy="job"`` ladder (trainer.py) takes over.

Compile-once discipline (the engine's ``decode`` rule applied to
training): each stage jits exactly one forward, one backward, one
grad-accumulate and one optimizer-apply program PER VIRTUAL CHUNK for
its life; the counters are asserted ==1 across recovery — survivors
never retrace and a replacement compiles each program exactly once in
its fresh process. The programs are AOT lowered+compiled (the
``StepProfiler.wrap_jit`` shape), so the XLA cost analysis feeds MFU
attribution for free, and the grad-accumulate/apply programs donate
their optimizer+param input buffers (rebound immediately after the
call; snapshots deep-copy for exactly this reason).

Step-time physics (ROADMAP item 5, the MFU attack):

  interleaved schedules — ``MPMDConfig.virtual_stages = v`` hosts v
      virtual chunks per stage actor (virtual stage vs = chunk*S + s),
      cutting the flush bubble from (S-1)/(M+S-1) toward
      (S-1)/(v*M+S-1); dispatch ref-chains the virtual-chunk dependency
      graph and per-chunk backward order stays microbatch-FIFO, so
      recovery replay and grad accumulation are bit-identical to the
      plain pipeline over the same V virtual stages.
  stage gangs — :class:`GangStageHandle` makes one stage a gang of
      workers over one multi-host mesh (the Podracer shape, slice
      acquisition folded in from ``backend_executor``): gang-consistent
      dispatch, activations enter/leave via rank 0's arena, digests
      gathered and compared across ranks, lifecycle unchanged.
  off-step I/O — step-boundary checkpoints snapshot to host on a
      background thread (``checkpoint_begin``/``checkpoint_result``)
      and durable shards seal/put through an ``AsyncShardWriter``;
      the only barriers are at recovery (rollback) and before the next
      donating apply. ``StepProfiler`` ("mpmd") attributes each step's
      compute/host-gap/data-wait and per-stage bubble as
      ``runtime_mpmd_*`` gauges and timeline spans.

Unit-tier shape: the controller talks to stages through a handle
protocol; :class:`LocalStageHandle` runs stages in-process (tests,
probes, the MULTICHIP dryrun with per-stage device subsets) while
:class:`ActorStageHandle` wraps a :class:`PipelineStageActor` gang —
same dispatcher, same recovery path.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu._private.config import cfg
from ray_tpu.parallel.pipeline import (OP_BWD, OP_FWD, make_schedule,
                                       op_chunk, peak_live_activations,
                                       pipeline_bubble_fraction)
from ray_tpu.train.config import FailureConfig


class StageLostError(RuntimeError):
    """One or more stage actors died or failed mid-step; carries the
    lost stage indexes (a single chaos event can take several stages —
    e.g. a node death under two colocated stages)."""

    def __init__(self, stage_idx: int, cause: str = "",
                 stages: Optional[List[int]] = None):
        self.stages = sorted(set(stages or [stage_idx]))
        super().__init__(f"pipeline stage(s) {self.stages} lost"
                         + (f": {cause}" if cause else ""))
        self.stage_idx = stage_idx
        self.cause = cause


class PipelineDegradedError(RuntimeError):
    """Stage-level recovery could not proceed (park-barrier deadline
    missed or failure budget exhausted); the pipeline is parked and the
    caller must fall back to a job-level restart."""


@dataclasses.dataclass
class StageDefinition:
    """What one pipeline stage computes. Built INSIDE the stage's
    process by the per-stage builder so params land on the stage's own
    mesh/devices.

    stage_fn(params, x) -> y; the last stage's ``loss_fn(y, targets)``
    -> scalar closes the pipeline. ``place`` re-places a restored host
    (numpy) state tree onto the stage's devices/shardings (defaults to
    leaving host arrays for jit to commit)."""
    stage_fn: Callable[[Any, Any], Any]
    params: Any
    optimizer: Any                                  # optax gradient xform
    loss_fn: Optional[Callable[[Any, Any], Any]] = None
    place: Optional[Callable[[Any], Any]] = None


@dataclasses.dataclass
class MPMDConfig:
    """Pipeline-shape + elasticity knobs (defaults from the flag
    registry, overridable per trainer)."""
    n_microbatches: int = 4
    schedule: str = "1f1b"                  # "1f1b" | "gpipe"
    virtual_stages: int = 1                 # v chunks per stage (1f1b only)
    replay_depth: Optional[int] = None      # cfg.mpmd_replay_depth
    checkpoint_every: Optional[int] = None  # default: replay_depth
    barrier_deadline_s: Optional[float] = None
    step_timeout_s: Optional[float] = None
    storage_path: Optional[str] = None      # durable shard checkpoints
    async_checkpoint: bool = True           # snapshot/seal off the hot path
    donate_buffers: bool = True             # donate opt+param apply inputs

    def resolved(self) -> "MPMDConfig":
        c = dataclasses.replace(self)
        if c.replay_depth is None:
            c.replay_depth = cfg.mpmd_replay_depth
        if c.checkpoint_every is None:
            c.checkpoint_every = c.replay_depth
        if c.barrier_deadline_s is None:
            c.barrier_deadline_s = cfg.mpmd_barrier_deadline_s
        if c.step_timeout_s is None:
            c.step_timeout_s = cfg.mpmd_step_timeout_s
        if c.n_microbatches < 1:
            raise ValueError("n_microbatches must be >= 1")
        if c.virtual_stages < 1:
            raise ValueError("virtual_stages must be >= 1")
        if c.virtual_stages > 1 and c.schedule != "1f1b":
            raise ValueError(
                "interleaved virtual stages require the '1f1b' schedule")
        if c.replay_depth < 1:
            raise ValueError("replay_depth must be >= 1")
        if c.checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")
        if c.checkpoint_every > c.replay_depth:
            raise ValueError(
                f"checkpoint_every={c.checkpoint_every} must be <= "
                f"replay_depth={c.replay_depth}: the replay buffer must "
                "cover every step since the last shard checkpoint")
        return c


# ------------------------------------------------------------ replay buffer

class MicrobatchReplayBuffer:
    """Bounded per-step retention of input microbatches (+ targets) so a
    re-provisioned stage can replay every step since the last shard
    checkpoint. Eviction is deterministic: strictly oldest-first once
    more than ``depth`` steps are held. Stored arrays are snapshotted
    (np.asarray copies) so later caller mutation can't corrupt replay.

    Sizing is accounted against the CORRECTED per-stage live-buffer
    peak (``peak_live_activations`` with grad-accumulation buffers
    included): the pipeline's worst-case microbatch-sized memory is the
    replay window (depth * M input microbatches held here) PLUS the
    busiest stage's in-flight stashes and grad buffers —
    ``budget()`` reports both so the controller sizes from the real
    number, not the activation-only undercount."""

    def __init__(self, depth: int, *, n_microbatches: Optional[int] = None,
                 peak_live_buffers: Optional[List[int]] = None):
        if depth < 1:
            raise ValueError("replay depth must be >= 1")
        self.depth = depth
        self.n_microbatches = n_microbatches
        self.peak_live_buffers = list(peak_live_buffers) \
            if peak_live_buffers is not None else None
        self._steps: Dict[int, Any] = {}

    def record(self, step: int, inputs: List[Any], targets: List[Any]):
        self._steps[step] = (
            [np.array(np.asarray(x)) for x in inputs],
            [np.array(np.asarray(t)) for t in targets])
        while len(self._steps) > self.depth:
            del self._steps[min(self._steps)]

    def budget(self) -> Dict[str, Any]:
        """Memory accounting for the replay window: bytes actually held
        plus the microbatch-buffer peak the pipeline adds on top."""
        held = sum(a.nbytes for ins, tgts in self._steps.values()
                   for a in (*ins, *tgts))
        out: Dict[str, Any] = {"depth": self.depth,
                               "steps_held": len(self._steps),
                               "bytes_held": int(held)}
        if self.n_microbatches is not None:
            out["replay_microbatches"] = self.depth * self.n_microbatches
            if self.peak_live_buffers:
                out["peak_live_stage_buffers"] = max(self.peak_live_buffers)
                out["peak_microbatch_buffers"] = (
                    out["replay_microbatches"]
                    + out["peak_live_stage_buffers"])
        return out

    def steps(self) -> List[int]:
        return sorted(self._steps)

    def get(self, step: int):
        if step not in self._steps:
            raise KeyError(
                f"step {step} not in replay buffer (held: {self.steps()}, "
                f"depth {self.depth})")
        return self._steps[step]

    def replayable_from(self, boundary_step: int) -> List[int]:
        """Steps after ``boundary_step`` available for replay, in order;
        raises if a gap means the boundary is too old to recover from."""
        want = [s for s in self.steps() if s > boundary_step]
        expect = list(range(boundary_step + 1, boundary_step + 1 + len(want)))
        if want != expect:
            raise KeyError(
                f"replay gap: checkpoint at step {boundary_step} but "
                f"buffer holds {self.steps()}")
        return want


# ------------------------------------------------------------ stage runtime

class _AotProgram:
    """Compile-once AOT wrapper around one jitted stage program (the
    ``StepProfiler.wrap_jit`` shape, instance-scoped): the first call
    per input shape traces/lowers/compiles exactly once — the
    trace-time compile counters fire there and only there — and later
    calls run the compiled executable directly, so there is no retrace
    surface at all. The XLA cost analysis is kept (``flops``/
    ``bytes_accessed``) for the trainer's MFU attribution. Backends
    that reject AOT fall back to the plain jitted callable (cost stays
    0, behavior identical)."""

    __slots__ = ("_jitted", "_cache", "flops", "bytes_accessed")

    def __init__(self, jitted):
        self._jitted = jitted
        self._cache: Dict[tuple, Any] = {}
        self.flops = 0.0
        self.bytes_accessed = 0.0

    def __call__(self, *args):
        from ray_tpu.util.profiling import _shape_key, cost_of_compiled
        try:
            key = _shape_key(args)
        except Exception:
            return self._jitted(*args)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._jitted
            try:
                import warnings
                with warnings.catch_warnings():
                    # donation is opportunistic: backends without buffer
                    # aliasing (CPU) ignore it, which is fine — silence
                    # the per-trace nag, the audit runs on TPU numbers
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable")
                    compiled = self._jitted.lower(*args).compile()
                cost = cost_of_compiled(compiled)
                self.flops = cost["flops"]
                self.bytes_accessed = cost["bytes_accessed"]
                fn = compiled
            except Exception:
                pass   # rtlint: disable=RT004 — plain jit fallback below
            self._cache[key] = fn
        try:
            return fn(*args)
        except Exception:
            if fn is self._jitted:
                raise
            # a strict AOT executable rejected this input (e.g. an
            # uncommitted sharding): pin the fallback for this shape
            self._cache[key] = self._jitted
            return self._jitted(*args)


class StageRuntime:
    """One stage's compute engine: compile-once fwd/bwd/accumulate/apply
    programs over the StageDefinition, saved-input bookkeeping for the
    recompute-style backward, grad accumulation in schedule order (replay
    determinism), and host-snapshot checkpoint/rollback. Runs unchanged
    inside a :class:`PipelineStageActor` or a :class:`LocalStageHandle`;
    under interleaved schedules a host holds one StageRuntime per
    virtual chunk, each with ``stage_idx`` = its VIRTUAL stage index.

    With ``donate=True`` the grad-accumulate program donates the old
    accumulator and the apply program donates params/opt_state/grads —
    all rebound immediately, so the only aliasing hazard is a host
    snapshot taken as a VIEW of a later-donated buffer; snapshots
    therefore always deep-copy (the donation-audit invariant the RT002
    lint rule guards statically).

    Checkpointing is asynchronous: ``checkpoint_begin`` captures the
    immutable param/opt_state trees and returns; a background thread
    materializes the host copy. ``checkpoint_result``/``rollback``/the
    next donating ``apply_step`` are the barrier points."""

    def __init__(self, defn: StageDefinition, *, stage_idx: int,
                 n_stages: int, n_microbatches: int, donate: bool = True):
        import jax

        self.defn = defn
        self.stage_idx = stage_idx
        self.n_stages = n_stages
        self.n_microbatches = n_microbatches
        self.donate = donate
        self.is_first = stage_idx == 0
        self.is_last = stage_idx == n_stages - 1
        if self.is_last and defn.loss_fn is None:
            raise ValueError("last stage needs a loss_fn")
        self.step = 0
        self.params = defn.params
        self.opt_state = defn.optimizer.init(defn.params)
        self.fwd_compile_count = 0
        self.bwd_compile_count = 0
        self.apply_compile_count = 0
        self._saved: Dict[tuple, Any] = {}
        self._gacc = None
        self._losses: List[Any] = []
        self._compute_s = 0.0
        self._op_s: Dict[str, float] = {}
        self._op_n: Dict[str, int] = {}
        self._ckpt_lock = threading.Lock()
        self._ckpt_thread: Optional[threading.Thread] = None
        self._ckpt_err: Optional[BaseException] = None
        self._last_snapshot = self._host_snapshot()

        stage_fn, loss_fn = defn.stage_fn, defn.loss_fn
        M = n_microbatches

        def fwd(params, x):
            self.fwd_compile_count += 1       # trace-time only
            return stage_fn(params, x)

        def fwd_last(params, x, target):
            self.fwd_compile_count += 1
            return loss_fn(stage_fn(params, x), target)

        def bwd(params, x, gy):
            self.bwd_compile_count += 1
            _y, vjp = jax.vjp(stage_fn, params, x)
            gp, gx = vjp(gy)
            return gx, gp

        def bwd_last(params, x, target):
            self.bwd_compile_count += 1
            loss, (gp, gx) = jax.value_and_grad(
                lambda p, xx: loss_fn(stage_fn(p, xx), target),
                argnums=(0, 1))(params, x)
            return gx, gp, loss

        def acc(a, b):
            return jax.tree.map(lambda u, v: u + v, a, b)

        def apply(params, opt_state, gacc):
            self.apply_compile_count += 1
            g = jax.tree.map(lambda u: u / M, gacc)
            updates, new_opt = defn.optimizer.update(g, opt_state,
                                                     params=params)
            import optax
            return optax.apply_updates(params, updates), new_opt

        # fwd/bwd inputs (params, activations) are reused across
        # microbatches — never donate those; the accumulator and the
        # optimizer/param buffers are consumed exactly once per call.
        donate_acc = {"donate_argnums": (0,)} if donate else {}
        donate_apply = {"donate_argnums": (0, 1, 2)} if donate else {}
        self._fwd_j = _AotProgram(jax.jit(fwd_last if self.is_last else fwd))
        self._bwd_j = _AotProgram(jax.jit(bwd_last if self.is_last else bwd))
        self._acc_j = _AotProgram(jax.jit(acc, **donate_acc))
        self._apply_j = _AotProgram(jax.jit(apply, **donate_apply))

    # ------------------------------------------------------------- compute
    def _timed(self, kind: str, fn, *args):
        import jax
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        self._compute_s += dt
        self._op_s[kind] = self._op_s.get(kind, 0.0) + dt
        self._op_n[kind] = self._op_n.get(kind, 0) + 1
        return out

    def flops_per_step(self) -> float:
        """One full step's FLOPs for this chunk from the compiled
        programs' cost analyses (0 until first execution / when the
        backend exposes no cost model)."""
        M = self.n_microbatches
        return (M * (self._fwd_j.flops + self._bwd_j.flops)
                + max(0, M - 1) * self._acc_j.flops
                + self._apply_j.flops)

    def forward(self, step: int, mb: int, x, target=None):
        """Run F(step, mb). Non-last stages return the activation (the
        object the next stage consumes); the last stage returns its
        per-microbatch loss. The input is saved for the recompute-style
        backward and dropped by it (or by abort_step). Outputs cross a
        MESH boundary, so they leave as host arrays — in-process that is
        the device→host hop the object-store hand-off pays anyway, and
        it keeps each stage's program free of the neighbor's placement."""
        if self.is_last:
            self._saved[(step, mb)] = (x, target)
            return np.asarray(self._timed("fwd", self._fwd_j, self.params,
                                          x, target))
        self._saved[(step, mb)] = x
        return np.asarray(self._timed("fwd", self._fwd_j, self.params, x))

    def backward(self, step: int, mb: int, gy=None):
        """Run B(step, mb): recompute-vjp over the saved input,
        accumulate param grads IN CALL ORDER (the schedule's order —
        replay hits the same order, hence bit-identical accumulation),
        return the input-gradient for the upstream stage (host array —
        it crosses the mesh boundary too)."""
        if self.is_last:
            x, target = self._saved.pop((step, mb))
            gx, gp, loss = self._timed("bwd", self._bwd_j, self.params, x,
                                       target)
            self._losses.append(np.asarray(loss))
        else:
            x = self._saved.pop((step, mb))
            gx, gp = self._timed("bwd", self._bwd_j, self.params, x, gy)
        self._gacc = gp if self._gacc is None \
            else self._timed("acc", self._acc_j, self._gacc, gp)
        return np.asarray(gx)

    def apply_step(self, step: int) -> Dict[str, Any]:
        """Step boundary: apply the accumulated (mean) gradient, clear
        per-step state, return stage metrics. Barriers any in-flight
        async snapshot first — apply DONATES the param/opt_state
        buffers, and the snapshot thread must not be copying them when
        their storage is reused."""
        if self._gacc is None:
            raise RuntimeError(f"stage {self.stage_idx}: apply_step({step}) "
                               "with no accumulated gradients")
        if self._saved:
            raise RuntimeError(
                f"stage {self.stage_idx}: {len(self._saved)} saved "
                f"activations outstanding at apply_step({step})")
        self._ckpt_barrier()
        self.params, self.opt_state = self._timed(
            "apply", self._apply_j, self.params, self.opt_state, self._gacc)
        metrics: Dict[str, Any] = {
            "step": step, "stage": self.stage_idx,
            "compute_s": round(self._compute_s, 6),
            "fwd_compile_count": self.fwd_compile_count,
            "bwd_compile_count": self.bwd_compile_count,
            "apply_compile_count": self.apply_compile_count,
            "flops": self.flops_per_step(),
        }
        for kind in ("fwd", "bwd"):
            metrics[f"{kind}_s"] = round(self._op_s.get(kind, 0.0), 6)
            metrics[f"{kind}_n"] = self._op_n.get(kind, 0)
        if self.is_last and self._losses:
            metrics["loss"] = float(np.mean([np.asarray(l)
                                             for l in self._losses]))
        self._gacc = None
        self._losses = []
        self._compute_s = 0.0
        self._op_s = {}
        self._op_n = {}
        self.step = step
        return metrics

    def abort_step(self, step: int) -> bool:
        """Park: drop the in-flight step's saved activations, partial
        grad accumulation and losses. Params/opt_state are untouched —
        they only move at apply_step."""
        self._saved = {k: v for k, v in self._saved.items()
                       if k[0] != step}
        self._gacc = None
        self._losses = []
        self._compute_s = 0.0
        self._op_s = {}
        self._op_n = {}
        return True

    # ------------------------------------------------------- checkpointing
    def _snapshot_of(self, step: int, params, opt_state) -> Dict[str, Any]:
        import jax
        # DEEP copies, not np.asarray views: a view would alias the very
        # device buffer the next apply_step DONATES, and XLA reusing the
        # storage would silently corrupt the snapshot (the
        # donated-buffer-reuse shape rtlint RT002 flags).
        def copy(a):
            return np.array(np.asarray(a))
        return {"step": step,
                "stage": self.stage_idx,
                "params": jax.tree.map(copy, params),
                "opt_state": jax.tree.map(copy, opt_state)}

    def _host_snapshot(self) -> Dict[str, Any]:
        return self._snapshot_of(self.step, self.params, self.opt_state)

    def _ckpt_barrier(self):
        """Join the in-flight async snapshot, surfacing its error."""
        t = self._ckpt_thread
        if t is not None:
            t.join()
            self._ckpt_thread = None
            if self._ckpt_err is not None:
                err, self._ckpt_err = self._ckpt_err, None
                raise RuntimeError(
                    f"stage {self.stage_idx}: async checkpoint "
                    "failed") from err

    def checkpoint_begin(self, step: int,
                         on_sealed: Optional[Callable] = None) -> bool:
        """Start a step-boundary shard snapshot OFF the hot path: the
        immutable param/opt_state trees are captured by reference (no
        copy on the caller's thread) and a background thread
        materializes the host copy — overlapping the next step's
        compute. ``on_sealed(snapshot)`` runs on that thread once the
        copy exists (the durable-shard writer hook)."""
        if step != self.step:
            raise RuntimeError(
                f"stage {self.stage_idx}: checkpoint({step}) at "
                f"step {self.step} — checkpoints are step-boundary only")
        self._ckpt_barrier()                  # one snapshot in flight max
        params, opt_state = self.params, self.opt_state

        def work():
            try:
                snap = self._snapshot_of(step, params, opt_state)
                with self._ckpt_lock:
                    self._last_snapshot = snap
                if on_sealed is not None:
                    on_sealed(snap)
            except BaseException as e:        # surfaced at the barrier
                self._ckpt_err = e

        self._ckpt_thread = threading.Thread(
            target=work, name=f"stage-{self.stage_idx}-ckpt", daemon=True)
        self._ckpt_thread.start()
        return True

    def checkpoint_result(self, step: int) -> Dict[str, Any]:
        """Barrier on the async snapshot and return it (the object the
        controller parks in the store for replacement stages)."""
        self._ckpt_barrier()
        with self._ckpt_lock:
            snap = self._last_snapshot
        if snap.get("step") != step:
            raise RuntimeError(
                f"stage {self.stage_idx}: checkpoint_result({step}) but "
                f"last snapshot is for step {snap.get('step')}")
        return snap

    def checkpoint(self, step: int) -> Dict[str, Any]:
        """Synchronous snapshot (begin + result) — the pre-async
        protocol, kept for callers that want the boundary cost inline."""
        self.checkpoint_begin(step)
        return self.checkpoint_result(step)

    def rollback(self) -> int:
        """Roll params/opt_state back to the last checkpoint boundary;
        returns the boundary step. Recovery is THE barrier point for
        async snapshots — an in-flight copy is joined first."""
        self._ckpt_barrier()
        with self._ckpt_lock:
            snap = self._last_snapshot
        self.load_snapshot(snap)
        return self.step

    def load_snapshot(self, snap: Dict[str, Any]):
        place = self.defn.place or (lambda t: t)
        self.params = place(snap["params"])
        self.opt_state = place(snap["opt_state"])
        self.step = int(snap["step"])
        self._last_snapshot = snap
        self._saved = {}
        self._gacc = None
        self._losses = []

    def state_digest(self) -> str:
        """sha256 over every params/opt_state leaf — the bit-identity
        probe the elastic tests compare against an uninterrupted run."""
        import jax
        h = hashlib.sha256()
        for tree in (self.params, self.opt_state):
            for leaf in jax.tree.leaves(tree):
                a = np.asarray(leaf)
                h.update(str(a.dtype).encode())
                h.update(str(a.shape).encode())
                h.update(a.tobytes())
        return h.hexdigest()

    def compile_counts(self) -> Dict[str, int]:
        return {"fwd": self.fwd_compile_count,
                "bwd": self.bwd_compile_count,
                "apply": self.apply_compile_count}


# ------------------------------------------------------------- stage hosts

def _build_definition(builder: Callable, stage_idx: int) -> StageDefinition:
    """Builders may take (stage_idx) or nothing."""
    try:
        import inspect
        takes_arg = len(inspect.signature(builder).parameters) >= 1
    except (TypeError, ValueError):
        takes_arg = True
    defn = builder(stage_idx) if takes_arg else builder()
    if not isinstance(defn, StageDefinition):
        raise TypeError(f"stage builder must return StageDefinition, "
                        f"got {type(defn)!r}")
    return defn


def _load_chunk_snapshots(rts: List[StageRuntime], snapshot):
    """Restore a host's runtimes from a snapshot: a single dict for the
    plain one-chunk host, a list (one per virtual chunk, chunk order)
    under interleaving."""
    snaps = [snapshot] if isinstance(snapshot, dict) else list(snapshot)
    if len(snaps) != len(rts):
        raise ValueError(
            f"snapshot has {len(snaps)} chunk shards, host has "
            f"{len(rts)} virtual chunks")
    for rt, snap in zip(rts, snaps):
        rt.load_snapshot(snap)


class _Now:
    """Pre-resolved 'future' for the in-process transport."""
    __slots__ = ("value", "error")

    def __init__(self, value=None, error: Optional[BaseException] = None):
        self.value = value
        self.error = error

    def result(self):
        if self.error is not None:
            raise self.error
        return self.value


class _Later:
    """Deferred 'future' for the in-process transport: the thunk runs
    on first fetch — how the local handles keep the async-checkpoint
    barrier OFF the hot path (the controller stores this unresolved
    and only resolves it on the recovery/restore path)."""
    __slots__ = ("_fn", "_done", "_value", "_error")

    def __init__(self, fn: Callable[[], Any]):
        self._fn = fn
        self._done = False
        self._value = None
        self._error: Optional[BaseException] = None

    def result(self):
        if not self._done:
            try:
                self._value = self._fn()
            except BaseException as e:
                self._error = e
            self._done = True
            self._fn = None
        if self._error is not None:
            raise self._error
        return self._value


class LocalStageHandle:
    """In-process stage host speaking the same protocol as the actor
    transport: every call returns a future (here pre-resolved), chaos
    injection runs at forward/backward entry (``stage_step`` spec — a
    fire marks the handle DEAD and every later call raises StageLostError,
    the in-process analog of a SIGKILLed actor), and ``preempting()``
    polls the per-stage marker file. ``fail_at=(step, op)`` arms a
    deterministic one-shot death for tests/probes."""

    remote = False

    def __init__(self, stage_idx: int, n_stages: int, n_microbatches: int,
                 builder: Optional[Callable] = None,
                 snapshot: Optional[Any] = None,
                 preempt_marker: Optional[str] = None,
                 fail_at: Optional[tuple] = None,
                 chunk_builders: Optional[List[tuple]] = None,
                 donate: bool = True):
        self.stage_idx = stage_idx
        if chunk_builders is None:
            chunk_builders = [(stage_idx, builder)]
        self._rts = [
            StageRuntime(_build_definition(b, vs), stage_idx=vs,
                         n_stages=n_stages, n_microbatches=n_microbatches,
                         donate=donate)
            for vs, b in chunk_builders]
        self._rt = self._rts[0]            # single-chunk back-compat alias
        if snapshot is not None:
            _load_chunk_snapshots(self._rts, snapshot)
        self._marker = preempt_marker
        self._fail_at = fail_at
        self._dead = False

    # ------------------------------------------------------ chaos plumbing
    def _chaos(self, step: int, op: str):
        if self._dead:
            raise StageLostError(self.stage_idx, "stage already dead")
        if self._fail_at is not None and self._fail_at == (step, op):
            self._fail_at = None
            self._dead = True
            raise StageLostError(self.stage_idx,
                                 f"armed failure at step {step} {op}")
        from ray_tpu._private import rpc
        try:
            rpc._maybe_inject_failure("stage_step")
        except rpc.RpcError as e:
            self._dead = True
            raise StageLostError(self.stage_idx, str(e)) from e

    def _call(self, fn, *args) -> _Now:
        try:
            return _Now(fn(*args))
        except BaseException as e:   # surfaced at fetch, like a ref
            return _Now(error=e)

    # ------------------------------------------------------------ protocol
    @staticmethod
    def _unwrap(v):
        # upstream outputs arrive as _Now futures; a poisoned one
        # re-raises the upstream loss here, mirroring how a failed
        # object-ref dependency fails the downstream actor task
        return v.result() if isinstance(v, _Now) else v

    def forward(self, step, mb, x, target=None, chunk=0) -> _Now:
        def run():
            self._chaos(step, OP_FWD)
            return self._rts[chunk].forward(step, mb, self._unwrap(x),
                                            target)
        return self._call(run)

    def backward(self, step, mb, gy=None, chunk=0) -> _Now:
        def run():
            self._chaos(step, OP_BWD)
            return self._rts[chunk].backward(step, mb, self._unwrap(gy))
        return self._call(run)

    def apply_step(self, step) -> _Now:
        def run():
            if self._dead:
                raise StageLostError(self.stage_idx, "stage already dead")
            return [rt.apply_step(step) for rt in self._rts]
        return self._call(run)

    def abort_step(self, step) -> _Now:
        if self._dead:
            return _Now(error=StageLostError(self.stage_idx, "dead"))
        return self._call(lambda: all([rt.abort_step(step)
                                       for rt in self._rts]))

    def checkpoint(self, step) -> _Now:
        if self._dead:
            return _Now(error=StageLostError(self.stage_idx, "dead"))
        return self._call(lambda: [rt.checkpoint(step) for rt in self._rts])

    def checkpoint_begin(self, step) -> _Now:
        if self._dead:
            return _Now(error=StageLostError(self.stage_idx, "dead"))
        return self._call(lambda: all([rt.checkpoint_begin(step)
                                       for rt in self._rts]))

    def checkpoint_result(self, step) -> _Later:
        # deferred: the barrier on the background snapshot happens at
        # fetch time (restore path), not on the training hot path
        return _Later(lambda: [rt.checkpoint_result(step)
                               for rt in self._rts])

    def rollback(self) -> _Now:
        if self._dead:
            return _Now(error=StageLostError(self.stage_idx, "dead"))

        def run():
            bounds = [rt.rollback() for rt in self._rts]
            if len(set(bounds)) != 1:
                raise RuntimeError(
                    f"stage {self.stage_idx}: virtual chunks rolled back "
                    f"to different boundaries {bounds}")
            return bounds[0]
        return self._call(run)

    def compile_counts(self) -> _Now:
        return self._call(lambda: [rt.compile_counts()
                                   for rt in self._rts])

    def state_digest(self) -> _Now:
        return self._call(lambda: [rt.state_digest() for rt in self._rts])

    def ping(self, timeout: Optional[float] = None) -> bool:
        return not self._dead

    def preempting(self) -> bool:
        if self._dead:
            return False
        if self._marker and os.path.exists(self._marker):
            return True
        from ray_tpu._private.accelerators.tpu import \
            check_preemption_notice
        return check_preemption_notice()

    def kill(self):
        self._dead = True

    def fetch(self, fut: _Now, timeout: Optional[float] = None):
        return fut.result()


class PipelineStageActor:
    """Actor hosting one pipeline stage pinned to its own mesh/slice.
    Compute methods ride the DEFAULT (ordered) concurrency group —
    dispatch order is execution order, which the replay-determinism
    guarantee leans on; control methods (ping/abort/rollback/...)
    declare the ``control`` group so the controller can park or probe a
    stage while compute is queued. Chaos: the ``stage_step`` injection
    SIGKILLs the process mid-step (``util.chaos.StageKiller``), the
    hardest death the recovery path must absorb."""

    def __init__(self, stage_idx: int, n_stages: int, n_microbatches: int,
                 builder: Optional[Callable] = None,
                 snapshot: Optional[Any] = None,
                 preempt_marker: Optional[str] = None,
                 chunk_builders: Optional[List[tuple]] = None,
                 donate: bool = True):
        if chunk_builders is None:
            chunk_builders = [(stage_idx, builder)]
        self._rts = [
            StageRuntime(_build_definition(b, vs), stage_idx=vs,
                         n_stages=n_stages, n_microbatches=n_microbatches,
                         donate=donate)
            for vs, b in chunk_builders]
        self._rt = self._rts[0]            # single-chunk back-compat alias
        if snapshot is not None:
            snapshot = self._materialize(snapshot)
            _load_chunk_snapshots(self._rts, snapshot)
        self._marker = preempt_marker
        self._preempting = False
        self._shard_writer = None
        self._stop = threading.Event()
        self._watch = threading.Thread(target=self._watch_loop,
                                       name=f"stage-{stage_idx}-watch",
                                       daemon=True)
        self._watch.start()

    @staticmethod
    def _materialize(snapshot):
        """Snapshots may arrive as object refs (broadcast restore) —
        per chunk or whole — depending on the restore ladder rung."""
        import ray_tpu

        def one(s):
            return s if s is None or isinstance(s, dict) else ray_tpu.get(s)
        if isinstance(snapshot, (list, tuple)):
            return [one(s) for s in snapshot]
        return one(snapshot)

    def _watch_loop(self):
        from ray_tpu._private.accelerators.tpu import \
            check_preemption_notice
        while not self._stop.is_set():
            try:
                if (self._marker and os.path.exists(self._marker)) \
                        or check_preemption_notice():
                    self._preempting = True
            except Exception:
                pass   # rtlint: disable=RT004 — poll again next tick
            if self._stop.wait(cfg.mpmd_health_poll_s):
                return

    def _chaos(self):
        from ray_tpu._private import rpc
        try:
            rpc._maybe_inject_failure("stage_step")
        except rpc.RpcError:
            # the chaos contract is a process DEATH mid-step, not a
            # catchable exception: survivors must recover from silence
            import signal
            os.kill(os.getpid(), signal.SIGKILL)

    # ------------------------------------------------------------- compute
    def forward(self, step, mb, x, target=None, chunk=0):
        self._chaos()
        return self._rts[chunk].forward(step, mb, x, target)

    def backward(self, step, mb, gy=None, chunk=0):
        self._chaos()
        return self._rts[chunk].backward(step, mb, gy)

    def apply_step(self, step):
        return [rt.apply_step(step) for rt in self._rts]

    def checkpoint(self, step):
        """Synchronous boundary snapshot (pre-async protocol)."""
        self.checkpoint_begin(step)
        return self.checkpoint_result(step)

    def checkpoint_begin(self, step):
        """Rides the ordered compute queue (so it lands exactly at the
        step boundary) but only captures references and hands the host
        copy + durable seal/put to background threads — the next step's
        compute is never behind a checkpoint write."""
        for rt in self._rts:
            rt.checkpoint_begin(step, on_sealed=self._sealed_hook(rt))
        return True

    def checkpoint_result(self, step):
        """Barrier + return the per-chunk snapshots (control group: the
        compute queue keeps draining while a caller waits here)."""
        return [rt.checkpoint_result(step) for rt in self._rts]

    def _sealed_hook(self, rt: StageRuntime):
        if not self._storage_dir():
            return None
        writer = self._ensure_shard_writer()
        root, vs = self._storage_path, rt.stage_idx

        def on_sealed(snap):
            writer.submit(root, vs, snap)
        return on_sealed

    def _ensure_shard_writer(self):
        if self._shard_writer is None:
            from ray_tpu.train.sharded_checkpoint import AsyncShardWriter
            self._shard_writer = AsyncShardWriter()
        return self._shard_writer

    def _storage_dir(self):
        return getattr(self, "_storage_path", None)

    def set_storage_path(self, path: Optional[str]):
        self._storage_path = path
        return True

    # ------------------------------------------------------------- control
    def abort_step(self, step):
        return all([rt.abort_step(step) for rt in self._rts])

    def rollback(self):
        # recovery is the shard-write barrier: a survivor's durable
        # state must be consistent before replay resumes over it
        if self._shard_writer is not None:
            try:
                self._shard_writer.barrier(timeout=60.0)
            except RuntimeError:
                import logging
                logging.getLogger(__name__).warning(
                    "stage %d: async shard write failed before rollback",
                    self._rts[0].stage_idx, exc_info=True)
        bounds = [rt.rollback() for rt in self._rts]
        if len(set(bounds)) != 1:
            raise RuntimeError(
                f"virtual chunks rolled back to different boundaries "
                f"{bounds}")
        return bounds[0]

    def compile_counts(self):
        return [rt.compile_counts() for rt in self._rts]

    def state_digest(self):
        return [rt.state_digest() for rt in self._rts]

    def ping(self):
        return True

    def preempting(self):
        return self._preempting

    def stop(self):
        self._stop.set()
        return True


# control methods answer while compute is queued: tag the group on the
# plain functions (actor.py reads __concurrency_group__ through
# ray_tpu.remote(), same as @ray_tpu.method(concurrency_group=...)).
# checkpoint_result is control-tagged on purpose: it BLOCKS on the
# background snapshot, and must not stall the ordered compute queue —
# checkpoint_begin stays on the compute queue so the capture lands
# exactly at the step boundary.
for _name in ("abort_step", "rollback", "compile_counts", "state_digest",
              "ping", "preempting", "stop", "set_storage_path",
              "checkpoint_result"):
    getattr(PipelineStageActor, _name).__concurrency_group__ = "control"
del _name


class ActorStageHandle:
    """Controller-side wrapper around a PipelineStageActor: methods
    return ObjectRefs (activations/grads stay in the object store — the
    controller passes refs between stages, never bytes)."""

    remote = True

    def __init__(self, stage_idx: int, actor):
        self.stage_idx = stage_idx
        self.actor = actor

    @classmethod
    def provision(cls, stage_idx: int, n_stages: int, n_microbatches: int,
                  builder: Optional[Callable] = None, snapshot=None,
                  preempt_marker: Optional[str] = None,
                  resources: Optional[Dict[str, float]] = None,
                  storage_path: Optional[str] = None,
                  chunk_builders: Optional[List[tuple]] = None,
                  donate: bool = True,
                  extra_options: Optional[Dict[str, Any]] = None
                  ) -> "ActorStageHandle":
        import ray_tpu
        opts: Dict[str, Any] = {
            "max_concurrency": 4,
            "concurrency_groups": {"control": 2},
        }
        if resources:
            opts["resources"] = dict(resources)
        if extra_options:
            opts.update(extra_options)
        actor = ray_tpu.remote(PipelineStageActor).options(**opts).remote(
            stage_idx, n_stages, n_microbatches, builder, snapshot,
            preempt_marker, chunk_builders, donate)
        h = cls(stage_idx, actor)
        if storage_path:
            h.fetch(actor.set_storage_path.remote(storage_path),
                    timeout=60.0)
        return h

    def forward(self, step, mb, x, target=None, chunk=0):
        return self.actor.forward.remote(step, mb, x, target, chunk)

    def backward(self, step, mb, gy=None, chunk=0):
        return self.actor.backward.remote(step, mb, gy, chunk)

    def apply_step(self, step):
        return self.actor.apply_step.remote(step)

    def abort_step(self, step):
        return self.actor.abort_step.remote(step)

    def checkpoint(self, step):
        return self.actor.checkpoint.remote(step)

    def checkpoint_begin(self, step):
        return self.actor.checkpoint_begin.remote(step)

    def checkpoint_result(self, step):
        return self.actor.checkpoint_result.remote(step)

    def rollback(self):
        return self.actor.rollback.remote()

    def compile_counts(self):
        return self.actor.compile_counts.remote()

    def state_digest(self):
        return self.actor.state_digest.remote()

    def ping(self, timeout: Optional[float] = 5.0) -> bool:
        import ray_tpu
        try:
            ray_tpu.get(self.actor.ping.remote(), timeout=timeout)
            return True
        except Exception:
            return False

    def preempting(self) -> bool:
        import ray_tpu
        try:
            return bool(ray_tpu.get(self.actor.preempting.remote(),
                                    timeout=5.0))
        except Exception:
            return False

    def kill(self):
        import ray_tpu
        try:
            ray_tpu.kill(self.actor)
        except Exception:
            pass

    def fetch(self, ref, timeout: Optional[float] = None):
        import ray_tpu
        return ray_tpu.get(ref, timeout=timeout)


# ------------------------------------------------------------- stage gangs

class _GangFanout:
    """Composite future over every gang member for one gang-consistent
    op: fetched as a unit (rank 0's value is the gang's value, the
    other ranks are verified/drained), plus the shadow futures of
    earlier rank-fanned compute ops that resolve at this barrier."""

    __slots__ = ("items", "shadow", "reduce")

    def __init__(self, items, shadow, reduce):
        self.items = items          # [(member, fut)] — values kept
        self.shadow = shadow        # [(member, fut)] — drained, discarded
        self.reduce = reduce        # List[value] -> gang value


class GangStageHandle:
    """One pipeline stage as a GANG of workers over one multi-host mesh
    — the Podracer slice-gang shape folded in from
    ``backend_executor`` (see :func:`acquire_slice_bundles`). Dispatch
    is gang-consistent: every compute op goes to ALL ranks in the same
    order, activations enter and leave through rank 0's arena (rank 0's
    output ref is what the neighbor stage consumes; the other ranks'
    outputs become shadow futures verified and drained at the step's
    apply barrier, so a straggler or diverged rank surfaces before the
    optimizer moves). State digests are gathered from every rank and
    must agree bit-for-bit; checkpoints ship rank 0's shard (the ranks
    are replicas of the same stage program). The preemption/park/replay
    lifecycle is unchanged — the gang fails, parks, restores and
    replays as a unit (any dead rank ⇒ the stage is lost ⇒ the whole
    gang is re-provisioned from the shard)."""

    def __init__(self, stage_idx: int, members: List[Any]):
        if not members:
            raise ValueError("a stage gang needs >= 1 member")
        self.stage_idx = stage_idx
        self.members = list(members)
        self.remote = bool(getattr(members[0], "remote", False))
        self._shadow: List[tuple] = []

    @classmethod
    def provision(cls, stage_idx: int, n_stages: int, n_microbatches: int,
                  chunk_builders: List[tuple], snapshot=None, *,
                  gang_size: int, topology: Optional[str] = None,
                  resources: Optional[Dict[str, float]] = None,
                  preempt_marker: Optional[str] = None,
                  storage_path: Optional[str] = None,
                  donate: bool = True) -> "GangStageHandle":
        """Provision a remote gang. With a ``topology``, the gang is
        pinned STRICT_SPREAD over one healthy multi-host slice via the
        executor's slice machinery; otherwise ranks schedule by
        ``resources`` alone."""
        per_rank_opts: List[Optional[Dict[str, Any]]] = \
            [None] * gang_size
        per_rank_res: List[Optional[Dict[str, float]]] = \
            [dict(resources) if resources else None] * gang_size
        if topology:
            from ray_tpu.train.backend_executor import acquire_slice_bundles
            from ray_tpu.util import (PlacementGroupSchedulingStrategy,
                                      placement_group)
            pod, bundles, strategy = acquire_slice_bundles(
                topology, resources or {}, num_workers=gang_size)
            if pod is not None:
                pg = placement_group(bundles, strategy=strategy)
                if not pg.wait(timeout=60):
                    raise RuntimeError(
                        f"stage {stage_idx}: gang placement group over "
                        f"{topology} not schedulable")
                per_rank_opts = [
                    {"scheduling_strategy": PlacementGroupSchedulingStrategy(
                        placement_group=pg, placement_group_bundle_index=r)}
                    for r in range(gang_size)]
                per_rank_res = [None] * gang_size   # the bundle carries it
        members = [
            ActorStageHandle.provision(
                stage_idx, n_stages, n_microbatches, None, snapshot,
                # only rank 0 watches the notice channel; preemption of
                # any gang host surfaces as a dead rank at the barrier
                preempt_marker=preempt_marker if r == 0 else None,
                resources=per_rank_res[r],
                storage_path=storage_path if r == 0 else None,
                chunk_builders=chunk_builders, donate=donate,
                extra_options=per_rank_opts[r])
            for r in range(gang_size)]
        return cls(stage_idx, members)

    # ------------------------------------------------------------- compute
    def _fanout_compute(self, submit) -> Any:
        futs = [submit(m) for m in self.members]
        self._shadow.extend(zip(self.members[1:], futs[1:]))
        return futs[0]

    def forward(self, step, mb, x, target=None, chunk=0):
        return self._fanout_compute(
            lambda m: m.forward(step, mb, x, target, chunk=chunk))

    def backward(self, step, mb, gy=None, chunk=0):
        return self._fanout_compute(
            lambda m: m.backward(step, mb, gy, chunk=chunk))

    def apply_step(self, step):
        shadow, self._shadow = self._shadow, []
        items = [(m, m.apply_step(step)) for m in self.members]

        def reduce(vals):
            norm = [[v] if isinstance(v, dict) else list(v) for v in vals]
            steps = {m.get("step") for chunks in norm for m in chunks}
            if len(steps) > 1:
                raise StageLostError(
                    self.stage_idx,
                    f"gang ranks applied different steps {sorted(steps)}")
            return norm[0]
        return _GangFanout(items, shadow, reduce)

    # ------------------------------------------------------------- control
    def abort_step(self, step):
        # parking discards the in-flight step everywhere, shadows too
        shadow, self._shadow = self._shadow, []
        items = [(m, m.abort_step(step)) for m in self.members]
        return _GangFanout(items, [], lambda vals: all(vals))

    def checkpoint(self, step):
        items = [(m, m.checkpoint(step)) for m in self.members]
        return _GangFanout(items, [], lambda vals: vals[0])

    def checkpoint_begin(self, step):
        # every rank snapshots (each needs its OWN boundary for
        # rollback); only rank 0's shard leaves the gang
        items = [(m, m.checkpoint_begin(step)) for m in self.members]
        return _GangFanout(items, [], lambda vals: all(vals))

    def checkpoint_result(self, step):
        # rank 0's arena is the gang's checkpoint arena
        return self.members[0].checkpoint_result(step)

    def rollback(self):
        items = [(m, m.rollback()) for m in self.members]

        def reduce(vals):
            if len(set(vals)) != 1:
                raise RuntimeError(
                    f"stage {self.stage_idx}: gang ranks rolled back to "
                    f"different boundaries {vals}")
            return vals[0]
        return _GangFanout(items, [], reduce)

    def compile_counts(self):
        items = [(m, m.compile_counts()) for m in self.members]
        return _GangFanout(items, [], lambda vals: vals[0])

    def state_digest(self):
        items = [(m, m.state_digest()) for m in self.members]

        def reduce(vals):
            norm = [[v] if isinstance(v, str) else list(v) for v in vals]
            if any(n != norm[0] for n in norm[1:]):
                raise RuntimeError(
                    f"stage {self.stage_idx}: gang rank states diverged "
                    "(replicated-stage invariant broken)")
            return norm[0]
        return _GangFanout(items, [], reduce)

    def ping(self, timeout: Optional[float] = 5.0) -> bool:
        return all(m.ping(timeout=timeout) for m in self.members)

    def preempting(self) -> bool:
        for m in self.members:
            try:
                if m.preempting():
                    return True
            except Exception:
                continue
        return False

    def kill(self):
        for m in self.members:
            try:
                m.kill()
            except Exception:
                pass   # rtlint: disable=RT004 — teardown best-effort

    def fetch(self, fut, timeout: Optional[float] = None):
        if isinstance(fut, _GangFanout):
            for m, f in fut.shadow:      # drain rank>0 compute outputs
                m.fetch(f, timeout=timeout)
            vals = [m.fetch(f, timeout=timeout) for m, f in fut.items]
            return fut.reduce(vals)
        return self.members[0].fetch(fut, timeout=timeout)


# -------------------------------------------------------------- controller

class MPMDPipelineTrainer:
    """Drives an S-stage MPMD pipeline over stage handles: ref-chained
    schedule dispatch, step-boundary shard checkpoints, and the
    stage-loss lifecycle (park → re-provision → restore → replay →
    rejoin).

    stage_builders: one callable per VIRTUAL stage returning its
        :class:`StageDefinition` (runs inside the stage's host process).
        With ``config.virtual_stages == v > 1`` the V = len(builders)
        virtual stages fold onto S = V // v physical stage hosts in the
        interleaved wrap: virtual stage vs lives on host vs % S as
        chunk vs // S.
    remote=True provisions a :class:`PipelineStageActor` gang (one
        actor per stage, ``stage_resources[s]`` pinning each to its
        slice); remote=False runs stages in-process (tests/probe).
    stage_gang_sizes[s] > 1 widens physical stage s into a
        :class:`GangStageHandle` of that many ranks (remote) or fake
        local members (in-process tests).
    provision_fn(stage_idx, snapshot) overrides stage provisioning
        entirely (tests inject failing handles through this)."""

    def __init__(self, stage_builders: List[Callable],
                 config: Optional[MPMDConfig] = None,
                 failure_config: Optional[FailureConfig] = None,
                 *, remote: bool = False,
                 stage_resources: Optional[List[Dict[str, float]]] = None,
                 stage_gang_sizes: Optional[List[int]] = None,
                 provision_fn: Optional[Callable] = None,
                 marker_dir: Optional[str] = None):
        self.builders = list(stage_builders)
        self.config = (config or MPMDConfig()).resolved()
        v = self.config.virtual_stages
        self.n_virtual = len(self.builders)
        if self.n_virtual % v:
            raise ValueError(
                f"virtual_stages={v} must divide the number of stage "
                f"builders ({self.n_virtual})")
        self.n_stages = self.n_virtual // v
        if self.n_stages < 2:
            raise ValueError("an MPMD pipeline needs >= 2 physical stages"
                             + (f" (got {self.n_virtual} builders at "
                                f"virtual_stages={v})" if v > 1 else ""))
        self.failure_config = failure_config or FailureConfig(
            max_failures=3, restart_policy="stage")
        self.remote = remote
        self.stage_resources = stage_resources or [None] * self.n_stages
        self.stage_gang_sizes = stage_gang_sizes or [1] * self.n_stages
        self._provision_fn = provision_fn
        self.schedule = make_schedule(self.config.schedule, self.n_stages,
                                      self.config.n_microbatches, virtual=v)
        self.replay = MicrobatchReplayBuffer(
            self.config.replay_depth,
            n_microbatches=self.config.n_microbatches,
            peak_live_buffers=[peak_live_activations(ops)
                               for ops in self.schedule])
        self.handles: List[Any] = []
        self.profiler = None
        self.last_stage_metrics: List[List[Dict[str, Any]]] = []
        self._snap_refs: Dict[int, Any] = {}   # stage -> snapshot ref/tree
        self._ckpt_step = 0
        self._failures_left = self.failure_config.max_failures
        self.recoveries: List[Dict[str, Any]] = []
        self.history: List[Dict[str, Any]] = []
        self._marker_dir = marker_dir
        self._markers: List[Optional[str]] = [None] * self.n_stages
        if marker_dir:
            os.makedirs(marker_dir, exist_ok=True)
            self._markers = [os.path.join(marker_dir, f"stage_{s}.preempt")
                             for s in range(self.n_stages)]

    # ---------------------------------------------------------- provision
    def _chunk_indices(self, stage_idx: int) -> List[int]:
        """Virtual-stage indices hosted by physical stage ``stage_idx``
        (the interleaved wrap: chunk c is virtual stage c*S + s)."""
        return [c * self.n_stages + stage_idx
                for c in range(self.config.virtual_stages)]

    def _provision(self, stage_idx: int, snapshot=None):
        if self._provision_fn is not None:
            return self._provision_fn(stage_idx, snapshot)
        return self._default_provision(stage_idx, snapshot)

    def _default_provision(self, stage_idx: int, snapshot=None):
        """The built-in stage host factory; provision_fn overrides can
        delegate here (it never re-enters the override)."""
        chunk_builders = [(vs, self.builders[vs])
                          for vs in self._chunk_indices(stage_idx)]
        gang = self.stage_gang_sizes[stage_idx]
        if self.remote:
            if gang > 1:
                return GangStageHandle.provision(
                    stage_idx, self.n_virtual, self.config.n_microbatches,
                    chunk_builders, snapshot, gang_size=gang,
                    resources=self.stage_resources[stage_idx],
                    preempt_marker=self._markers[stage_idx],
                    storage_path=self.config.storage_path,
                    donate=self.config.donate_buffers)
            return ActorStageHandle.provision(
                stage_idx, self.n_virtual, self.config.n_microbatches,
                None, snapshot,
                preempt_marker=self._markers[stage_idx],
                resources=self.stage_resources[stage_idx],
                storage_path=self.config.storage_path,
                chunk_builders=chunk_builders,
                donate=self.config.donate_buffers)
        if gang > 1:
            members = [LocalStageHandle(
                stage_idx, self.n_virtual, self.config.n_microbatches,
                None, snapshot,
                preempt_marker=self._markers[stage_idx] if r == 0 else None,
                chunk_builders=chunk_builders,
                donate=self.config.donate_buffers)
                for r in range(gang)]
            return GangStageHandle(stage_idx, members)
        return LocalStageHandle(
            stage_idx, self.n_virtual, self.config.n_microbatches,
            None, snapshot,
            preempt_marker=self._markers[stage_idx],
            chunk_builders=chunk_builders,
            donate=self.config.donate_buffers)

    def start(self):
        """Provision the stage gang and take the step-0 checkpoint (so a
        loss before the first boundary can still restore)."""
        if self.handles:
            return self
        self.handles = [self._provision(s) for s in range(self.n_stages)]
        self._checkpoint_all(0)
        return self

    def preempt_marker(self, stage_idx: int) -> Optional[str]:
        """The per-stage notice-file path (chaos/StageKiller channel)."""
        return self._markers[stage_idx]

    # -------------------------------------------------------------- fit
    def fit(self, data_fn: Callable[[int], tuple], n_steps: int
            ) -> Dict[str, Any]:
        """Run ``n_steps`` pipeline steps. ``data_fn(step)`` returns
        (inputs, targets): M first-stage input microbatches and M
        last-stage target microbatches. Returns the run summary."""
        from ray_tpu._private import events
        self.start()
        if self.profiler is None:           # runtime_mpmd_* attribution
            from ray_tpu.util.profiling import StepProfiler
            self.profiler = StepProfiler(name="mpmd", category="train")
        with events.record_span("train.mpmd.fit", category="train",
                                n_stages=self.n_stages,
                                n_virtual=self.n_virtual,
                                n_microbatches=self.config.n_microbatches,
                                schedule=self.config.schedule):
            step = 0
            while step < n_steps:
                step += 1
                scope = self.profiler.step()
                inputs, targets = data_fn(step)
                self._check_shapes(inputs, targets)
                self.replay.record(step, inputs, targets)
                scope.data_ready()
                self._run_step_with_recovery(step, inputs, targets)
                scope.__exit__(None, None, None)
                # checkpoint + migration run OUTSIDE the step scope: with
                # async_checkpoint they cost one fast ref round-trip here
                # and the residue shows up as the NEXT step's host_gap —
                # exactly the off-step signal the profiler attributes
                if step % self.config.checkpoint_every == 0:
                    self._checkpoint_all(step)
                self._migrate_preempting(step)
        return self.summary()

    def _check_shapes(self, inputs, targets):
        M = self.config.n_microbatches
        if len(inputs) != M or len(targets) != M:
            raise ValueError(
                f"data_fn must return {M} input + {M} target microbatches "
                f"(got {len(inputs)}/{len(targets)})")

    def summary(self) -> Dict[str, Any]:
        last = self.history[-1] if self.history else {}
        v = self.config.virtual_stages
        return {"steps": len({h["step"] for h in self.history}),
                "last_metrics": last,
                "history": self.history,
                "recoveries": self.recoveries,
                "schedule": self.config.schedule,
                "virtual_stages": v,
                "bubble_fraction_analytic": pipeline_bubble_fraction(
                    self.n_stages, self.config.n_microbatches, virtual=v),
                "bubble_fraction_analytic_plain": pipeline_bubble_fraction(
                    self.n_stages, self.config.n_microbatches),
                "peak_live_activations": [
                    peak_live_activations(ops) for ops in self.schedule],
                "replay_budget": self.replay.budget()}

    # ------------------------------------------------------ step execution
    def _run_step_with_recovery(self, step, inputs, targets):
        """Run one step; on stage loss, recover (park → replace →
        rollback) and replay the buffer — a loss DURING replay loops
        back into recovery against the same budget, so repeated chaos
        converges or degrades deterministically."""
        try:
            self._run_step(step, inputs, targets)
            return
        except StageLostError as e:
            lost, cause = e.stages, e.cause
        while True:
            t_rec = time.perf_counter()
            boundary = self._prepare_recovery(step, lost, cause)
            try:
                replayed = self.replay.replayable_from(boundary)
                for t in replayed:
                    ins, tgts = self.replay.get(t)
                    self._run_step(t, ins, tgts)
            except StageLostError as e:
                lost, cause = e.stages, e.cause
                continue
            self._note_recovery(step, lost, cause, boundary, replayed,
                                time.perf_counter() - t_rec)
            return

    def _run_step(self, step, inputs, targets):
        """Dispatch one step's full schedule ref-chained, then collect
        the per-stage apply barrier (per-chunk metrics per stage)."""
        from ray_tpu._private import events
        t0 = time.perf_counter()
        apply_futs = self._dispatch(step, inputs, targets)
        metrics = self._collect_applies(step, apply_futs)
        wall = time.perf_counter() - t0
        self.last_stage_metrics = metrics
        row: Dict[str, Any] = {"step": step, "wall_s": round(wall, 6)}
        total_flops = 0.0
        total_compute = 0.0
        for s, per_chunk in enumerate(metrics):
            comp = sum(m.get("compute_s", 0.0) for m in per_chunk)
            total_compute += comp
            total_flops += sum(m.get("flops", 0.0) for m in per_chunk)
            row[f"stage{s}_compute_s"] = round(comp, 6)
            row[f"stage{s}_bubble_fraction"] = round(
                max(0.0, 1.0 - comp / wall), 4) if wall else 0.0
            for m in per_chunk:
                if "loss" in m:
                    row["loss"] = m["loss"]
        self.history.append(row)
        if self.profiler is not None:
            if total_flops:
                self.profiler.set_cost(total_flops)
            self._emit_stage_gauges(row, wall, total_compute)
        events.record_instant(
            "train.mpmd.step", category="train", step=step,
            wall_ms=round(wall * 1e3, 3),
            **({"loss": row["loss"]} if "loss" in row else {}))
        return row

    def _emit_stage_gauges(self, row, wall, total_compute):
        """Per-stage compute/bubble/transfer attribution as
        ``runtime_mpmd_*`` gauges (the PR 7 gauges cover the step as a
        whole; these break the step open by physical stage)."""
        from ray_tpu.util.metrics import Gauge
        if not hasattr(self, "_stage_gauges"):
            self._stage_gauges = {
                "compute_ms": Gauge(
                    "runtime_mpmd_stage_compute_ms",
                    "per-stage on-device compute in the last step",
                    tag_keys=("stage",)),
                "bubble": Gauge(
                    "runtime_mpmd_stage_bubble_fraction",
                    "per-stage idle fraction of the last step wall",
                    tag_keys=("stage",)),
                "transfer_ms": Gauge(
                    "runtime_mpmd_transfer_ms",
                    "step wall not attributed to any stage's compute "
                    "(activation transfer + dispatch + collectives)"),
            }
        for s in range(self.n_stages):
            tags = {"stage": str(s)}
            self._stage_gauges["compute_ms"].set(
                row.get(f"stage{s}_compute_s", 0.0) * 1e3, tags=tags)
            self._stage_gauges["bubble"].set(
                row.get(f"stage{s}_bubble_fraction", 0.0), tags=tags)
        # stages overlap in time, so Σ compute can exceed wall; clamp —
        # the unclamped signal still lives in the per-stage gauges
        self._stage_gauges["transfer_ms"].set(
            max(0.0, wall - total_compute) * 1e3)

    def _dispatch(self, step, inputs, targets):
        """Ref-chain the schedule over the virtual-chunk dependency
        graph: virtual stage vs = c*S + s consumes activations from
        vs-1 (hosted on stage (vs-1) % S — possibly the SAME host's
        previous chunk) and gradients from vs+1. Keys are virtual-stage
        indices, so the plain path (v=1, vs == s) is unchanged."""
        S = self.n_stages
        V = self.n_virtual
        queues = [list(ops) for ops in self.schedule]
        fwd_out: Dict[tuple, Any] = {}
        bwd_out: Dict[tuple, Any] = {}
        while any(queues):
            progressed = False
            for s in range(S):
                while queues[s]:
                    op = queues[s][0]
                    kind, mb, c = op[0], op[1], op_chunk(op)
                    vs = c * S + s
                    if kind == OP_FWD:
                        if vs == 0:
                            x = inputs[mb]
                        elif (vs - 1, mb) in fwd_out:
                            x = fwd_out[(vs - 1, mb)]
                        else:
                            break
                        tgt = targets[mb] if vs == V - 1 else None
                        fwd_out[(vs, mb)] = self.handles[s].forward(
                            step, mb, x, tgt, chunk=c)
                    else:
                        if vs < V - 1 and (vs + 1, mb) not in bwd_out:
                            break
                        gy = bwd_out[(vs + 1, mb)] if vs < V - 1 else None
                        bwd_out[(vs, mb)] = self.handles[s].backward(
                            step, mb, gy, chunk=c)
                    queues[s].pop(0)
                    progressed = True
            if not progressed:
                raise ValueError("pipeline schedule deadlocked in dispatch")
        return [h.apply_step(step) for h in self.handles]

    def _collect_applies(self, step, apply_futs):
        """Fetch every stage's apply barrier. Returns one per-chunk
        metrics LIST per stage (single-chunk handles that return a bare
        dict are normalized)."""
        metrics, first_err = [], None
        for s, fut in enumerate(apply_futs):
            try:
                got = self.handles[s].fetch(
                    fut, timeout=self.config.step_timeout_s)
                metrics.append([got] if isinstance(got, dict) else list(got))
            except Exception as e:
                if first_err is None:
                    first_err = (s, e)
        if first_err is not None:
            lost = [s for s, h in enumerate(self.handles)
                    if not h.ping(timeout=5.0)]
            raise StageLostError(
                lost[0] if lost else first_err[0],
                f"{type(first_err[1]).__name__}: {first_err[1]}",
                stages=lost or [first_err[0]])
        return metrics

    # ------------------------------------------------------- checkpointing
    def _checkpoint_all(self, step):
        """Step-boundary checkpoint of every stage. Async mode
        (config.async_checkpoint) splits the protocol: fetch the cheap
        ``checkpoint_begin`` acks (capture happens at the boundary, the
        host copy runs on each stage's background thread), then store
        the ``checkpoint_result`` futures UNRESOLVED — the barrier that
        waits for the sealed snapshot moves to the recovery path."""
        if self.config.async_checkpoint:
            begun = [(s, h.checkpoint_begin(step))
                     for s, h in enumerate(self.handles)]
            for s, fut in begun:
                self.handles[s].fetch(fut, timeout=60.0)
            for s, h in enumerate(self.handles):
                self._snap_refs[s] = h.checkpoint_result(step)
        else:
            futs = [h.checkpoint(step) for h in self.handles]
            for s, fut in enumerate(futs):
                if self.handles[s].remote:
                    # keep the REF: the snapshot object stays in the
                    # arena (cross-node restores ride the data plane);
                    # fetching it to the controller would defeat the
                    # zero-copy path
                    self._snap_refs[s] = fut
                else:
                    self._snap_refs[s] = self.handles[s].fetch(fut)
        self._ckpt_step = step

    def _resolve_snap(self, stage_idx: int):
        """Materialize a stored snapshot entry for a LOCAL restore
        (async mode parks _Later/_Now thunks; resolving one is the
        recovery-time barrier)."""
        snap = self._snap_refs.get(stage_idx)
        if snap is not None and hasattr(snap, "result"):
            snap = self._snap_refs[stage_idx] = snap.result()
        return snap

    def _restore_source(self, stage_idx: int):
        """Recovery ladder for a replacement stage's shard: object-store
        snapshot ref first; durable storage shard (one host reads, the
        weight plane fans out — sharded_checkpoint.restore_and_broadcast)
        when the ref is gone."""
        remote = bool(self.handles and
                      getattr(self.handles[stage_idx], "remote", False))
        if not remote:
            snap = self._resolve_snap(stage_idx)
        else:
            snap = self._snap_refs.get(stage_idx)
            if snap is not None:
                try:
                    # probe the ref is still materializable (the dead
                    # stage's node may have taken it down with it)
                    import ray_tpu
                    ready, _ = ray_tpu.wait([snap], num_returns=1,
                                            timeout=5.0)
                    if not ready:
                        snap = None
                except Exception:
                    snap = None
        if snap is not None:
            return snap
        if self.config.storage_path:
            from ray_tpu.train.sharded_checkpoint import (
                restore_stage_shard)
            shards = [restore_stage_shard(self.config.storage_path, vs,
                                          broadcast=self.remote)
                      for vs in self._chunk_indices(stage_idx)]
            return shards[0] if len(shards) == 1 else shards
        raise PipelineDegradedError(
            f"no restore source for stage {stage_idx} (snapshot ref lost "
            "and no storage_path configured)")

    # ------------------------------------------------------------ recovery
    def _prepare_recovery(self, step, lost: List[int], cause: str = ""
                          ) -> int:
        """Budget check → park survivors at the bounded barrier →
        re-provision lost stages from their shards → roll survivors back
        to the checkpoint boundary. Returns the boundary step the replay
        must start after. Raises PipelineDegradedError when stage-level
        recovery cannot proceed (policy/budget/barrier)."""
        from ray_tpu._private import events
        policy = getattr(self.failure_config, "restart_policy", "job")
        if policy != "stage":
            raise PipelineDegradedError(
                f"stage {lost} lost at step {step} and "
                f"restart_policy={policy!r}: job-level restart required")
        if self._failures_left <= 0:
            raise PipelineDegradedError(
                f"stage {lost} lost at step {step}: failure budget "
                f"exhausted (max_failures="
                f"{self.failure_config.max_failures})")
        self._failures_left -= 1
        events.record_instant(
            "train.mpmd.stage_lost", category="train", step=step,
            stages=",".join(map(str, lost)), cause=cause[:200])
        time.sleep(getattr(self.failure_config, "restart_backoff_s", 0.0)
                   or 0.0)

        # 1. park survivors at the bounded-deadline barrier
        survivors = [s for s in range(self.n_stages) if s not in lost]
        deadline = time.monotonic() + self.config.barrier_deadline_s
        barrier = [(s, self.handles[s].abort_step(step)) for s in survivors]
        stragglers = []
        for s, fut in barrier:
            left = deadline - time.monotonic()
            try:
                self.handles[s].fetch(fut, timeout=max(0.1, left))
            except Exception:
                stragglers.append(s)
        if stragglers:
            raise PipelineDegradedError(
                f"survivors {stragglers} missed the "
                f"{self.config.barrier_deadline_s}s park barrier after "
                f"stage {lost} loss — degrading to job-level restart")

        # 2. re-provision lost stages from their shard checkpoints
        for s in lost:
            try:
                self.handles[s].kill()
            except Exception:
                pass   # rtlint: disable=RT004 — corpse may be gone
            self.handles[s] = self._provision(s, self._restore_source(s))

        # 3. roll surviving stages back to the checkpoint boundary
        boundary = self._ckpt_step
        roll = [(s, self.handles[s].rollback()) for s in survivors]
        for s, fut in roll:
            got = self.handles[s].fetch(fut, timeout=60.0)
            if got != boundary:
                raise PipelineDegradedError(
                    f"stage {s} rolled back to step {got}, controller "
                    f"checkpoint boundary is {boundary}")
        return boundary

    def _note_recovery(self, step, lost, cause, boundary, replayed,
                       recovery_s):
        from ray_tpu._private import events
        self.recoveries.append({
            "step": step, "stages": list(lost), "cause": cause,
            "boundary": boundary, "replayed_steps": list(replayed),
            "steps_lost": len(replayed),
            "recovery_s": round(recovery_s, 3)})
        events.record_instant(
            "train.mpmd.stage_rejoined", category="train", step=step,
            stages=",".join(map(str, lost)), boundary=boundary,
            steps_replayed=len(replayed),
            recovery_ms=round(recovery_s * 1e3, 1))

    # --------------------------------------------------- graceful migration
    def _migrate_preempting(self, step):
        """Boundary-time migration for stages whose host got a
        preemption NOTICE (watch thread / marker file): fresh
        checkpoint, replacement provisioned from it, old actor reaped —
        zero replayed steps, optimizer state untouched."""
        preempting = []
        for s, h in enumerate(self.handles):
            try:
                if h.preempting():
                    preempting.append(s)
            except Exception:
                continue
        if not preempting:
            return
        from ray_tpu._private import events
        self._checkpoint_all(step)
        for s in preempting:
            old = self.handles[s]
            snap = (self._snap_refs[s] if old.remote
                    else self._resolve_snap(s))
            self.handles[s] = self._provision(s, snap)
            try:
                old.kill()
            except Exception:
                pass   # rtlint: disable=RT004 — host is going away anyway
            if self._markers[s]:
                try:
                    os.remove(self._markers[s])
                except FileNotFoundError:
                    pass
            events.record_instant(
                "train.mpmd.stage_migrated", category="train", step=step,
                stage=s)

    # ------------------------------------------------------------- queries
    def _flatten_virtual(self, per_stage: List[Any]) -> List[Any]:
        """Reorder per-stage per-chunk lists into VIRTUAL-stage order
        (out[c*S + s] = stage s's chunk c) — the order a plain v=1 run
        over V single-chunk stages would report, so digests compare
        directly across schedules."""
        S, v = self.n_stages, self.config.virtual_stages
        norm = [[x] if not isinstance(x, list) else x for x in per_stage]
        out: List[Any] = [None] * self.n_virtual
        for s, chunks in enumerate(norm):
            if len(chunks) != v:
                raise RuntimeError(
                    f"stage {s} reported {len(chunks)} chunks, "
                    f"expected {v}")
            for c, val in enumerate(chunks):
                out[c * S + s] = val
        return out

    def compile_counts(self) -> List[Dict[str, int]]:
        """Per-VIRTUAL-stage compile counters (virtual-stage order)."""
        futs = [h.compile_counts() for h in self.handles]
        got = [self.handles[s].fetch(f, timeout=30.0)
               for s, f in enumerate(futs)]
        return self._flatten_virtual(got)

    def state_digests(self) -> List[str]:
        """Per-VIRTUAL-stage state digests (virtual-stage order) —
        directly comparable between a v>1 run and a plain run over the
        same V builders."""
        futs = [h.state_digest() for h in self.handles]
        got = [self.handles[s].fetch(f, timeout=60.0)
               for s, f in enumerate(futs)]
        return self._flatten_virtual(got)

    def shutdown(self):
        for h in self.handles:
            members = getattr(h, "members", [h])
            for m in members:
                try:
                    if m.remote and hasattr(m, "actor"):
                        m.fetch(m.actor.stop.remote(), timeout=5.0)
                    m.kill()
                except Exception:
                    pass   # rtlint: disable=RT004 — teardown best-effort
        self.handles = []
