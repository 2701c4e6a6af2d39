"""Elastic MPMD pipeline training (train/mpmd.py): per-stage programs,
1F1B/GPipe host schedules, bounded replay, and stage-level preemption
recovery.

Unit tier (any interpreter, in-process LocalStageHandle):
  - schedule generation + dependency-order simulation (pipeline.py)
  - replay-buffer determinism + bounded eviction + gap detection
  - stage kill mid-step → park → restore → replay → BIT-IDENTICAL
    optimizer state vs the uninterrupted baseline, compile counts ==1
  - barrier deadline miss / exhausted budget → controlled degrade
    (PipelineDegradedError), never a hang
  - graceful preemption-notice migration at a step boundary
  - FailureConfig restart_policy plumbing + BackendExecutor
    supports_worker_replace gating
  - StageKiller chaos spec + stage shard save/restore helpers

Cluster tier (Python >= 3.12): a real PipelineStageActor gang with a
stage actor killed mid-step, and JaxTrainer per-worker replace under
restart_policy="stage".
"""

import os
import time

import numpy as np
import pytest

from ray_tpu.parallel import pipeline as plib
from ray_tpu.train.config import FailureConfig
from ray_tpu.train.mpmd import (LocalStageHandle, MicrobatchReplayBuffer,
                                MPMDConfig, MPMDPipelineTrainer,
                                PipelineDegradedError, StageDefinition,
                                StageLostError)
from ray_tpu.util.chaos import StageKiller

D, MB, M, S = 8, 4, 4, 3


# ------------------------------------------------------------- schedules

def test_1f1b_counts_and_order():
    for s_n, m_n in [(2, 2), (3, 4), (4, 8), (2, 1), (5, 3)]:
        sched = plib.schedule_1f1b(s_n, m_n)
        assert len(sched) == s_n
        for ops in sched:
            fwd = [mb for op, mb in ops if op == plib.OP_FWD]
            bwd = [mb for op, mb in ops if op == plib.OP_BWD]
            assert fwd == list(range(m_n))     # F in microbatch order
            assert bwd == list(range(m_n))     # B in microbatch order


def test_1f1b_peak_live_below_gpipe():
    s_n, m_n = 4, 8
    f1b = plib.schedule_1f1b(s_n, m_n)
    gp = plib.schedule_gpipe(s_n, m_n)
    for s in range(s_n):
        # gpipe peaks at M stashes during warmup (grad buffer only
        # becomes live once stashes are draining, so M stays the peak)
        assert plib.peak_live_activations(gp[s]) == m_n
        # 1f1b steady state: min(S-s, M) stashes + the grad-accumulation
        # buffer held from first backward to the apply
        assert plib.peak_live_activations(f1b[s]) == min(s_n - s, m_n) + 1
        # legacy activation-only count (what the buffer sizing used to
        # be computed from — one short per stage)
        assert plib.peak_live_activations(f1b[s], grad_buffers=False) == \
            min(s_n - s, m_n)


def test_peak_live_pinned_s4_m8_interleaved():
    """Satellite pin: steady-state buffer peaks for (S=4, M=8) at
    v=1 and v=2 — the numbers MicrobatchReplayBuffer.budget() sizes
    peak_microbatch_buffers from."""
    assert [plib.peak_live_activations(ops)
            for ops in plib.make_schedule("1f1b", 4, 8)] == [5, 4, 3, 2]
    assert [plib.peak_live_activations(ops)
            for ops in plib.make_schedule("1f1b", 4, 8, virtual=2)] == \
        [13, 11, 9, 7]


def test_schedules_simulate_without_deadlock():
    for kind in ("1f1b", "gpipe"):
        for s_n, m_n in [(2, 2), (3, 4), (4, 8)]:
            order = plib.simulate_schedule(
                plib.make_schedule(kind, s_n, m_n))
            assert len(order) == 2 * s_n * m_n
            done = set()
            for _tick, s, op, mb, _chunk in order:
                if op == plib.OP_FWD:
                    assert s == 0 or (s - 1, "F", mb) in done
                else:
                    assert (s, "F", mb) in done
                    assert s == s_n - 1 or (s + 1, "B", mb) in done
                done.add((s, op, mb))


def test_interleaved_schedule_simulates_without_deadlock():
    """Deadlock-freedom for v in {2, 3} across divisible and
    non-divisible M (closed form and greedy fallback paths)."""
    for v in (2, 3):
        for s_n, m_n in [(2, 4), (4, 8), (3, 4), (4, 6), (2, 8)]:
            sched = plib.schedule_interleaved_1f1b(s_n, m_n, v)
            order = plib.simulate_schedule(sched)
            assert len(order) == 2 * v * s_n * m_n
            done = set()
            for _tick, s, op, mb, chunk in order:
                vs = chunk * s_n + s
                if op == plib.OP_FWD:
                    assert vs == 0 or (vs - 1, "F", mb) in done
                else:
                    assert (vs, "F", mb) in done
                    assert vs == v * s_n - 1 or (vs + 1, "B", mb) in done
                done.add((vs, op, mb))
            # each chunk's F and B streams stay in microbatch order —
            # the bit-identity invariant replay depends on
            for s in range(s_n):
                for c in range(v):
                    for kind in (plib.OP_FWD, plib.OP_BWD):
                        mbs = [op[1] for op in sched[s]
                               if op[0] == kind and plib.op_chunk(op) == c]
                        assert mbs == list(range(m_n))


def test_interleaved_closed_form_meets_analytic_bound():
    """When M % S == 0 the Megatron closed form must hit
    (S-1)/(v*M+S-1) exactly under the unit-time event model."""
    for v in (2, 3):
        for s_n, m_n in [(2, 4), (4, 8), (3, 6)]:
            sched = plib.schedule_interleaved_1f1b(s_n, m_n, v)
            tl = plib.simulate_timeline(sched, lambda s, k, c: 1.0)
            ideal = 2.0 * v * m_n  # per-stage busy ticks
            bound = plib.pipeline_bubble_fraction(s_n, m_n, virtual=v)
            assert tl["span"] == pytest.approx(ideal / (1.0 - bound),
                                               rel=1e-9)


def test_simulate_schedule_detects_deadlock():
    # backward before its own forward can never become ready
    bad = [[(plib.OP_BWD, 0), (plib.OP_FWD, 0)],
           [(plib.OP_FWD, 0), (plib.OP_BWD, 0)]]
    with pytest.raises(ValueError, match="deadlock"):
        plib.simulate_schedule(bad)


def test_make_schedule_validates():
    with pytest.raises(ValueError):
        plib.make_schedule("zigzag", 2, 2)
    with pytest.raises(ValueError):
        plib.schedule_1f1b(0, 4)
    assert plib.pipeline_bubble_fraction(4, 8) == pytest.approx(3 / 11)


# ---------------------------------------------------------- replay buffer

def test_replay_buffer_bounded_oldest_first():
    buf = MicrobatchReplayBuffer(depth=2)
    for t in range(1, 5):
        buf.record(t, [np.full((2,), t)], [np.full((2,), -t)])
    assert buf.steps() == [3, 4]
    ins, tgts = buf.get(3)
    np.testing.assert_array_equal(ins[0], np.full((2,), 3))
    np.testing.assert_array_equal(tgts[0], np.full((2,), -3))
    with pytest.raises(KeyError):
        buf.get(2)


def test_replay_buffer_snapshots_inputs():
    buf = MicrobatchReplayBuffer(depth=2)
    x = np.zeros((3,))
    buf.record(1, [x], [x])
    x[:] = 99.0                      # caller mutation after record
    ins, _ = buf.get(1)
    np.testing.assert_array_equal(ins[0], np.zeros((3,)))


def test_replay_buffer_gap_detection():
    buf = MicrobatchReplayBuffer(depth=2)
    buf.record(5, [np.zeros(1)], [np.zeros(1)])
    buf.record(6, [np.zeros(1)], [np.zeros(1)])
    assert buf.replayable_from(4) == [5, 6]
    assert buf.replayable_from(5) == [6]
    with pytest.raises(KeyError, match="gap"):
        buf.replayable_from(2)      # steps 3..4 already evicted


# ----------------------------------------------------------- local gangs

def _builder(stage_idx):
    import jax
    import jax.numpy as jnp
    import optax
    k = jax.random.PRNGKey(stage_idx)
    params = {"w": jax.random.normal(k, (D, D)) * 0.3,
              "b": jnp.zeros((D,))}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    loss_fn = None
    if stage_idx == S - 1:
        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)
    return StageDefinition(stage_fn=stage_fn, params=params,
                           optimizer=optax.adamw(1e-2), loss_fn=loss_fn)


def _data_fn(step):
    rng = np.random.RandomState(step)
    ins = [rng.randn(MB, D).astype(np.float32) for _ in range(M)]
    tgts = [rng.randn(MB, D).astype(np.float32) for _ in range(M)]
    return ins, tgts


def _trainer(max_failures=2, **cfg_kw):
    cfg_kw.setdefault("n_microbatches", M)
    return MPMDPipelineTrainer(
        [_builder] * S, MPMDConfig(**cfg_kw),
        FailureConfig(max_failures=max_failures, restart_policy="stage",
                      restart_backoff_s=0.0))


def test_local_pipeline_trains_and_compiles_once():
    tr = _trainer()
    out = tr.fit(_data_fn, 5)
    assert out["steps"] == 5
    losses = [h["loss"] for h in out["history"] if "loss" in h]
    assert len(losses) == 5 and all(np.isfinite(losses))
    for counts in tr.compile_counts():
        assert counts == {"fwd": 1, "bwd": 1, "apply": 1}
    # per-stage bubble + peak-live bookkeeping present
    assert out["peak_live_activations"] == [min(S - s, M) + 1
                                            for s in range(S)]
    assert 0.0 < out["bubble_fraction_analytic"] < 1.0
    assert "stage0_bubble_fraction" in out["history"][0]


def test_gpipe_schedule_also_trains():
    tr = _trainer(schedule="gpipe")
    out = tr.fit(_data_fn, 2)
    assert out["steps"] == 2
    assert out["peak_live_activations"] == [M] * S


def test_stage_kill_recovers_bit_identical():
    """The acceptance criterion: a stage killed mid-step recovers
    without restarting survivors, resumes within replay_depth + 1
    steps, and post-replay optimizer state is bit-identical."""
    base = _trainer()
    base.fit(_data_fn, 6)
    base_digests = base.state_digests()

    tr = _trainer(replay_depth=2)
    tr.start()
    survivors_before = [tr.handles[0], tr.handles[2]]
    tr.handles[1]._fail_at = (4, "F")          # dies mid-step 4
    out = tr.fit(_data_fn, 6)
    assert len(out["recoveries"]) == 1
    rec = out["recoveries"][0]
    assert rec["stages"] == [1]
    assert rec["steps_lost"] <= tr.config.replay_depth + 1
    assert rec["boundary"] == 2                # checkpoint_every=replay=2
    # survivors were never re-provisioned
    assert tr.handles[0] is survivors_before[0]
    assert tr.handles[2] is survivors_before[1]
    # state parity with the uninterrupted run, bit for bit
    assert tr.state_digests() == base_digests
    for counts in tr.compile_counts():
        assert counts["fwd"] == 1 and counts["bwd"] == 1


def test_kill_during_backward_also_recovers():
    base = _trainer()
    base.fit(_data_fn, 4)
    tr = _trainer()
    tr.start()
    tr.handles[2]._fail_at = (3, "B")          # last stage, backward
    tr.fit(_data_fn, 4)
    assert tr.recoveries and tr.recoveries[0]["stages"] == [2]
    assert tr.state_digests() == base.state_digests()


def test_failure_budget_exhaustion_degrades():
    tr = _trainer(max_failures=1)
    tr.start()

    # every provisioned replacement for stage 1 dies immediately too
    def chaos_provision(idx, snapshot=None):
        h = tr._default_provision(idx, snapshot)
        if idx == 1:
            h._fail_at = (3, "F")
        return h
    tr._provision_fn = chaos_provision
    tr.handles[1]._fail_at = (3, "F")
    with pytest.raises(PipelineDegradedError, match="budget"):
        tr.fit(_data_fn, 6)


def test_job_policy_refuses_stage_recovery():
    tr = MPMDPipelineTrainer(
        [_builder] * S, MPMDConfig(n_microbatches=M),
        FailureConfig(max_failures=3, restart_policy="job"))
    tr.start()
    tr.handles[1]._fail_at = (1, "F")
    with pytest.raises(PipelineDegradedError, match="job"):
        tr.fit(_data_fn, 2)


def test_barrier_deadline_miss_degrades():
    """A survivor that cannot park within the deadline turns the
    recovery into a controlled job-level degrade instead of a hang."""
    tr = _trainer(barrier_deadline_s=0.2)

    class StuckHandle(LocalStageHandle):
        def abort_step(self, step):
            from ray_tpu.train.mpmd import _Now
            return _Now(error=TimeoutError("survivor wedged"))

    def provision(idx, snapshot=None):
        if idx == 0:
            return StuckHandle(idx, S, M, _builder, snapshot)
        return tr._default_provision(idx, snapshot)
    tr._provision_fn = provision
    tr.start()
    tr.handles[1]._fail_at = (1, "F")
    t0 = time.monotonic()
    with pytest.raises(PipelineDegradedError, match="barrier"):
        tr.fit(_data_fn, 2)
    assert time.monotonic() - t0 < 10.0


def test_preempt_notice_migrates_at_boundary(tmp_path):
    """The graceful path: a notice (marker file) migrates the stage at
    the next step boundary — no replay, no recovery entry, and the
    run's final state matches the unperturbed baseline bit for bit."""
    base = _trainer()
    base.fit(_data_fn, 4)

    tr = MPMDPipelineTrainer(
        [_builder] * S, MPMDConfig(n_microbatches=M),
        FailureConfig(max_failures=2, restart_policy="stage",
                      restart_backoff_s=0.0),
        marker_dir=str(tmp_path))
    tr.start()
    old = tr.handles[1]
    done = []

    def data_fn(step):
        if step == 3 and not done:
            done.append(1)
            StageKiller.preempt_stage(tr.preempt_marker(1))
        return _data_fn(step)

    out = tr.fit(data_fn, 4)
    assert tr.handles[1] is not old           # migrated
    assert old._dead                          # old host reaped
    assert out["recoveries"] == []            # no crash recovery
    assert not os.path.exists(tr.preempt_marker(1))   # notice cleared
    assert tr.state_digests() == base.state_digests()


def test_stage_killer_chaos_spec_degrades_controlled():
    """stage_step=1.0 kills every (re)provisioned stage's first forward:
    recovery burns the budget and must end in PipelineDegradedError —
    the controlled degrade, not a hang or an unhandled crash."""
    killer = StageKiller(probability=1.0)
    assert killer.spec() == "stage_step=1.0"
    env = killer.env({})
    assert env[StageKiller.SPEC_ENV] == "stage_step=1.0"
    tr = _trainer(max_failures=2)
    tr.start()                                 # provision BEFORE arming
    killer.arm_local()
    try:
        with pytest.raises(PipelineDegradedError, match="budget"):
            tr.fit(_data_fn, 3)
    finally:
        StageKiller.disarm_local()


def test_stage_killer_single_shot_recovers():
    """Arm before step 2, disarm when the controller provisions the
    first replacement (the 'node came back clean' shape) — the pipeline
    recovers and finishes training."""
    killer = StageKiller(probability=1.0)
    tr = _trainer(max_failures=3)
    tr.start()

    def provision(idx, snapshot=None):
        StageKiller.disarm_local()      # replacement host is clean
        return tr._default_provision(idx, snapshot)
    tr._provision_fn = provision
    armed = []

    def data_fn(step):
        if step == 2 and not armed:
            armed.append(1)
            killer.arm_local()
        return _data_fn(step)

    try:
        out = tr.fit(data_fn, 4)
    finally:
        StageKiller.disarm_local()
    assert out["recoveries"], "chaos never fired"
    # with p=1 the whole gang died at once; every stage was replaced
    assert out["recoveries"][0]["stages"] == [0, 1, 2]
    assert out["steps"] == 4


# ------------------------------------------------- restore-source ladder

def test_stage_shard_save_restore_roundtrip(tmp_path):
    from ray_tpu.train.sharded_checkpoint import (restore_stage_shard,
                                                  save_stage_shard)
    snap = {"step": 7, "stage": 1,
            "params": {"w": np.arange(6, dtype=np.float32)},
            "opt_state": {"m": np.ones(3)}}
    save_stage_shard(str(tmp_path), 1, snap)
    back = restore_stage_shard(str(tmp_path), 1)
    assert back["step"] == 7
    np.testing.assert_array_equal(back["params"]["w"],
                                  snap["params"]["w"])


def test_recovery_falls_back_to_storage_shard(tmp_path):
    """Snapshot ref lost with the stage's node → the replacement
    restores from the durable storage shard instead."""
    from ray_tpu.train.sharded_checkpoint import save_stage_shard
    base = _trainer()
    base.fit(_data_fn, 4)

    tr = _trainer(storage_path=str(tmp_path))
    tr.start()
    # step-boundary checkpoints: persist each stage's snapshot like the
    # actor's checkpoint() does when storage_path is set
    orig_ckpt = tr._checkpoint_all

    def ckpt_and_persist(step):
        orig_ckpt(step)
        for s in list(tr._snap_refs):
            # async checkpoints park unresolved futures; the durable
            # write needs the sealed snapshot (the actor's shard writer
            # gets it from the on_sealed hook)
            save_stage_shard(str(tmp_path), s, tr._resolve_snap(s))
    tr._checkpoint_all = ckpt_and_persist
    ckpt_and_persist(0)
    tr.handles[1]._fail_at = (3, "F")
    # simulate the in-memory snapshot dying with the stage
    orig_restore = tr._restore_source

    def restore(stage_idx):
        tr._snap_refs.pop(stage_idx, None)
        return orig_restore(stage_idx)
    tr._restore_source = restore
    tr.fit(_data_fn, 4)
    assert tr.recoveries
    assert tr.state_digests() == base.state_digests()


def test_no_restore_source_degrades():
    tr = _trainer()
    tr.start()
    tr.handles[1]._fail_at = (2, "F")
    tr._snap_refs.clear()
    with pytest.raises(PipelineDegradedError, match="restore source"):
        tr.fit(_data_fn, 3)


# ------------------------------------------- interleaved virtual stages

V4 = 4          # virtual stages for the interleaving tests


def _vbuilder(vs):
    """Builder for an n_virtual=4 pipeline: one tanh layer per virtual
    stage, loss on the deepest chunk."""
    import jax
    import jax.numpy as jnp
    import optax
    k = jax.random.PRNGKey(100 + vs)
    params = {"w": jax.random.normal(k, (D, D)) * 0.3,
              "b": jnp.zeros((D,))}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    loss_fn = None
    if vs == V4 - 1:
        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)
    return StageDefinition(stage_fn=stage_fn, params=params,
                           optimizer=optax.adamw(1e-2), loss_fn=loss_fn)


def _vtrainer(virtual_stages, **cfg_kw):
    cfg_kw.setdefault("n_microbatches", M)
    return MPMDPipelineTrainer(
        [_vbuilder] * V4,
        MPMDConfig(virtual_stages=virtual_stages, **cfg_kw),
        FailureConfig(max_failures=2, restart_policy="stage",
                      restart_backoff_s=0.0))


def test_interleaved_matches_plain_bit_identical():
    """The tentpole invariant: v=2 over 2 hosts runs each chunk's F and
    B streams in strict microbatch order, so optimizer state is
    bit-identical to the SAME 4 virtual stages spread plainly over 4
    hosts — and every chunk still compiles exactly once."""
    plain = _vtrainer(virtual_stages=1)
    out_p = plain.fit(_data_fn, 4)
    inter = _vtrainer(virtual_stages=2)
    out_i = inter.fit(_data_fn, 4)
    assert plain.n_stages == 4 and inter.n_stages == 2
    assert inter.state_digests() == plain.state_digests()
    loss_p = [h["loss"] for h in out_p["history"] if "loss" in h]
    loss_i = [h["loss"] for h in out_i["history"] if "loss" in h]
    assert loss_i == loss_p
    for counts in inter.compile_counts():
        assert counts == {"fwd": 1, "bwd": 1, "apply": 1}
    # interleaving shrinks the analytic bubble
    assert out_i["bubble_fraction_analytic"] < \
        out_p["bubble_fraction_analytic"]


def test_interleaved_kill_recovery_bit_identical():
    """A stage hosting TWO chunks dies mid-step: both chunks restore
    from the same boundary, replay in the interleaved order, and the
    final state matches the uninterrupted run bit-for-bit. The rebuilt
    chunks compile once each (fresh runtimes, no retrace churn)."""
    base = _vtrainer(virtual_stages=2)
    base.fit(_data_fn, 6)

    tr = _vtrainer(virtual_stages=2, replay_depth=2)
    tr.start()
    tr.handles[1]._fail_at = (4, "F")          # chunks 1 and 3 die
    out = tr.fit(_data_fn, 6)
    assert len(out["recoveries"]) == 1
    assert tr.state_digests() == base.state_digests()
    for counts in tr.compile_counts():
        assert counts == {"fwd": 1, "bwd": 1, "apply": 1}


# ------------------------------------------------------ fake stage gangs

def test_local_gang_trains_and_matches_solo():
    """A 2-rank gang on stage 1 (fake: two in-process members) must be
    invisible to training semantics: same digests as the solo run, and
    the gang handle fans every compute op out to both ranks."""
    solo = _trainer()
    solo.fit(_data_fn, 4)

    gang = MPMDPipelineTrainer(
        [_builder] * S, MPMDConfig(n_microbatches=M),
        FailureConfig(max_failures=2, restart_policy="stage",
                      restart_backoff_s=0.0),
        stage_gang_sizes=[1, 2, 1])
    gang.fit(_data_fn, 4)
    h = gang.handles[1]
    assert hasattr(h, "members") and len(h.members) == 2
    assert gang.state_digests() == solo.state_digests()
    # both ranks actually ran the stage program (replicas, not spares)
    for m in h.members:
        for rt in m._rts:
            assert rt.step == 4
            assert rt.compile_counts() == {"fwd": 1, "bwd": 1, "apply": 1}


def test_gang_rank_divergence_detected():
    """The replicated-stage invariant: digests are gathered from every
    rank and must agree bit-for-bit — a silently diverged rank raises
    instead of corrupting the next boundary."""
    import jax
    tr = MPMDPipelineTrainer(
        [_builder] * S, MPMDConfig(n_microbatches=M),
        FailureConfig(max_failures=2, restart_policy="stage",
                      restart_backoff_s=0.0),
        stage_gang_sizes=[1, 2, 1])
    tr.fit(_data_fn, 2)
    rt = tr.handles[1].members[1]._rts[0]
    rt.params = jax.tree.map(lambda x: x + 1.0, rt.params)
    with pytest.raises(RuntimeError, match="diverged"):
        tr.state_digests()


# ------------------------------------- off-step I/O and donation parity

def test_async_checkpoint_and_donation_parity():
    """Async off-step checkpointing and buffer donation are pure
    performance knobs: all three configurations land bit-identical
    optimizer state. The async run must also park its boundary
    snapshots UNRESOLVED (no step-path barrier)."""
    base = _trainer()                                   # async + donate on
    base.fit(_data_fn, 5)
    assert all(hasattr(r, "result") for r in base._snap_refs.values())

    sync = _trainer(async_checkpoint=False)
    sync.fit(_data_fn, 5)
    nodonate = _trainer(donate_buffers=False)
    nodonate.fit(_data_fn, 5)
    assert sync.state_digests() == base.state_digests()
    assert nodonate.state_digests() == base.state_digests()


def test_replay_budget_reports_peak_buffers():
    """Satellite: the replay buffer is sized against the CORRECTED
    peak (grad buffers included), and budget() reports the composite
    microbatch-buffer number the controller reasons about."""
    peaks = [plib.peak_live_activations(ops)
             for ops in plib.make_schedule("1f1b", 4, 8, virtual=2)]
    assert peaks == [13, 11, 9, 7]
    buf = MicrobatchReplayBuffer(depth=2, n_microbatches=8,
                                 peak_live_buffers=peaks)
    buf.record(1, [np.zeros((2, 2))] * 8, [np.zeros((2, 2))] * 8)
    b = buf.budget()
    assert b["replay_microbatches"] == 16
    assert b["peak_live_stage_buffers"] == 13
    assert b["peak_microbatch_buffers"] == 29
    assert b["bytes_held"] == 8 * 2 * (2 * 2 * 8)   # 16 float64 4-elt arrays


# ----------------------------------------------------- config validation

def test_mpmd_config_validation():
    with pytest.raises(ValueError, match="checkpoint_every"):
        MPMDConfig(replay_depth=2, checkpoint_every=3).resolved()
    with pytest.raises(ValueError, match="n_microbatches"):
        MPMDConfig(n_microbatches=0).resolved()
    c = MPMDConfig().resolved()
    assert c.checkpoint_every == c.replay_depth


def test_failure_config_validation():
    with pytest.raises(ValueError, match="restart_policy"):
        FailureConfig(restart_policy="worker")
    with pytest.raises(ValueError, match="backoff"):
        FailureConfig(restart_backoff_s=-1.0)
    fc = FailureConfig(max_failures=2, restart_policy="stage")
    assert fc.restart_policy == "stage"


def test_trainer_requires_two_stages():
    with pytest.raises(ValueError, match="2 physical stages"):
        MPMDPipelineTrainer([_builder], MPMDConfig(n_microbatches=M))


def test_backend_executor_replace_gating():
    from ray_tpu.train.backend_executor import BackendExecutor
    from ray_tpu.train.config import ScalingConfig
    ex = BackendExecutor(ScalingConfig(num_workers=2))
    assert ex.supports_worker_replace()
    ex_jd = BackendExecutor(ScalingConfig(num_workers=2),
                            use_jax_distributed=True)
    assert not ex_jd.supports_worker_replace()
    ex_slice = BackendExecutor(ScalingConfig(num_workers=2))
    ex_slice.slice_pod = "pod-0"       # slice gangs fail as a unit
    assert not ex_slice.supports_worker_replace()


# ------------------------------------------------------- cluster tier

def test_actor_gang_stage_kill_bit_identical():
    """Real PipelineStageActor gang: stage 1's actor is SIGKILLed
    mid-run; recovery restores its shard from the object store and the
    final state matches the in-process uninterrupted baseline bit for
    bit (same programs, same data, same schedule)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    try:
        base = _trainer()
        base.fit(_data_fn, 5)

        tr = MPMDPipelineTrainer(
            [_builder] * S, MPMDConfig(n_microbatches=M, replay_depth=2),
            FailureConfig(max_failures=2, restart_policy="stage",
                          restart_backoff_s=0.0),
            remote=True)
        tr.start()
        killed = []

        def data_fn(step):
            if step == 3 and not killed:
                killed.append(1)
                import threading

                def kill_soon():
                    time.sleep(0.05)       # land mid-step
                    ray_tpu.kill(tr.handles[1].actor)
                threading.Thread(target=kill_soon, daemon=True).start()
            return _data_fn(step)

        out = tr.fit(data_fn, 5)
        assert out["recoveries"], "kill never surfaced as a stage loss"
        assert out["recoveries"][0]["steps_lost"] <= \
            tr.config.replay_depth + 1
        assert tr.state_digests() == base.state_digests()
        for counts in tr.compile_counts():
            assert counts["fwd"] == 1 and counts["bwd"] == 1
        tr.shutdown()
    finally:
        ray_tpu.shutdown()


def test_jax_trainer_per_worker_replace():
    """restart_policy="stage": a worker whose loop raises once is
    replaced in its bundle and resumes from the latest checkpoint; the
    fit completes without surfacing the failure."""
    import ray_tpu
    from ray_tpu.train import (Checkpoint, JaxTrainer, RunConfig,
                               ScalingConfig)
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    try:
        import tempfile
        marker = os.path.join(tempfile.mkdtemp(), "died_once")

        def loop(config):
            from ray_tpu import train
            ckpt = train.get_checkpoint()
            start = 0
            if ckpt is not None:
                start = ckpt.to_dict()["step"] + 1
            for step in range(start, 6):
                if step == 3 and not os.path.exists(config["marker"]):
                    with open(config["marker"], "w") as f:
                        f.write("x")
                    raise RuntimeError("injected worker death")
                train.report({"step": step},
                             checkpoint=Checkpoint.from_dict(
                                 {"step": step}))

        trainer = JaxTrainer(
            loop, train_loop_config={"marker": marker},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                failure_config=FailureConfig(
                    max_failures=2, restart_policy="stage",
                    restart_backoff_s=0.1)))
        result = trainer.fit()
        assert result.error is None, result.error
        assert result.metrics["step"] == 5
        steps = [m["step"] for m in result.metrics_history]
        assert steps[-1] == 5
        # the replacement resumed from the checkpoint, not step 0:
        # step 3 appears at most twice (once failed pre-report, once
        # after resume), never the full prefix again
        assert steps.count(0) == 1
    finally:
        ray_tpu.shutdown()
