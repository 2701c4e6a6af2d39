"""Step profiler (ray_tpu/util/profiling.py): phase attribution,
cost_analysis via the AOT wrap, gauge emission (the acceptance "CPU
train loop emits per-step MFU gauges with compute/host-gap
attribution"), and the engine decode / RL learner / make_train_fns
wiring. CPU-only, no cluster."""

import time

import numpy as np
import pytest

from ray_tpu._private import events
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import profiling


@pytest.fixture(autouse=True)
def _clean_recorder():
    events.drain()
    yield
    events.drain()


def _snap(prefix):
    return {m["name"]: m for m in metrics_mod.registry_snapshot()
            if m["name"].startswith(prefix)}


def test_attribution_phases_split_sanely():
    prof = profiling.StepProfiler("probe_attrib", emit_span=False,
                                  peak_flops=1e9, peak_bytes_per_s=1e9)
    prof.set_cost(flops=1e6, bytes_accessed=1e6)
    for _ in range(2):
        with prof.step(tokens=100) as s:
            time.sleep(0.02)            # data wait
            s.data_ready()
            time.sleep(0.04)            # compute
        time.sleep(0.01)                # host gap (before next step)
    rec = prof.last
    assert rec["data_wait_ms"] >= 15.0
    assert rec["compute_ms"] >= 30.0
    assert rec["host_gap_ms"] >= 5.0
    assert rec["wall_ms"] == pytest.approx(
        rec["compute_ms"] + rec["data_wait_ms"] + rec["host_gap_ms"],
        abs=0.01)
    # mfu over wall < mfu over compute alone; roofline from intensity
    assert 0 < rec["mfu"] < rec["mfu_compute"]
    assert rec["roofline_bound"] == 1.0     # intensity == machine balance
    assert rec["tokens_per_s"] > 0


def test_wrap_jit_cost_analysis_and_result_parity():
    import jax
    import jax.numpy as jnp
    prof = profiling.StepProfiler("probe_wrap", emit_span=False)

    def f(x, y):
        return x @ y

    j = jax.jit(f)
    wrapped = prof.wrap_jit(j)
    x = jnp.ones((64, 32))
    y = jnp.ones((32, 16))
    out = wrapped(x, y)
    assert out.shape == (64, 16)
    assert np.allclose(np.asarray(out), np.asarray(j(x, y)))
    assert prof.flops > 0               # cost analysis landed
    # second shape compiles its own entry with its own cost
    first = prof.flops
    wrapped(jnp.ones((8, 32)), y)
    assert prof.flops != first


def test_cpu_train_loop_emits_mfu_gauges_with_attribution():
    """Acceptance slice: a CPU train loop (make_train_fns + profiler)
    emits runtime_train_step_mfu and per-phase attribution gauges."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_fns

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, n_kv_heads=2, d_ff=64,
                            max_seq_len=32)
    mesh = make_mesh(MeshConfig(data=1))
    prof = profiling.StepProfiler("train_step", emit_span=True)
    init, step, _ = make_train_fns(TransformerLM(cfg), optax.adam(1e-3),
                                   mesh, batch_shape=(2, 16),
                                   profiler=prof)
    state = init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, 64, (2, 16)), jnp.int32)
    losses = []
    for _ in range(3):
        state, m = step(state, toks)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert prof.flops > 0, "cost_analysis did not land"
    rec = prof.last
    assert rec["mfu"] > 0 and "compute_ms" in rec and "host_gap_ms" in rec
    snap = _snap("runtime_train_step")
    assert snap["runtime_train_step_mfu"]["samples"][0][1] == rec["mfu"]
    phases = {dict(k)["phase"]: v for k, v in
              snap["runtime_train_step_phase_ms"]["samples"]}
    assert set(phases) == {"compute", "data_wait", "host_gap"}
    # and the per-step spans landed on the flight recorder
    names = [r["name"] for r in events.drain()
             if r.get("state") == "RUNNING"]
    assert names.count("train_step.step") == 3


def test_engine_decode_emits_mfu_and_span_attribution():
    """The step's one record carries the step's phases where the dense
    model's MFU guess stood: every `engine.decode` span has `plan_ms`,
    `dispatch_ms`, `read_ms`, `emit_ms` and `between_ms`, and the four
    tile the span."""
    import jax

    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import TransformerConfig
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, n_kv_heads=2, d_ff=64,
                            max_seq_len=32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    eng = InferenceEngine(model, params,
                          EngineConfig(n_slots=2, max_len=32,
                                       prefill_chunk=8,
                                       prefill_budget=16))
    h = eng.submit([1, 2, 3, 4], max_new_tokens=6)
    while eng.step():
        pass
    assert len(h.tokens()) == 6
    assert eng.decode_compile_count == 1    # the marks must not retrace
    assert not hasattr(eng, "profiler")
    rows = events.drain()
    ends = {r["task_id"]: r["ts"] for r in rows
            if r.get("state") == "FINISHED"}
    decs = [r for r in rows
            if r.get("state") == "RUNNING" and r["name"] == "engine.decode"]
    assert decs, "no decode spans"
    keys = ("plan_ms", "dispatch_ms", "read_ms", "emit_ms", "between_ms")
    for d in decs:
        attrs = d["attrs"]
        assert all(attrs[k] >= 0 for k in keys), attrs
        assert not {"mfu", "mfu_compute", "compute_ms", "host_gap_ms",
                    "data_wait_ms", "roofline_bound"} & set(attrs)
        span_ms = (ends[d["task_id"]] - d["ts"]) * 1e3
        # the phases tile the step, and the record is the step's: within
        # 1% (the record ends at the step's own last mark)
        assert sum(attrs[k] for k in keys) == pytest.approx(
            span_ms + attrs["between_ms"], rel=0.01, abs=2e-3)
    assert decs[0]["attrs"]["between_ms"] >= 0
    assert any(d["attrs"]["dispatch_ms"] > 0 and d["attrs"]["read_ms"] > 0
               for d in decs)
    assert not _snap("runtime_decode_step")


def test_rl_learner_emits_update_mfu():
    from ray_tpu.rl.learner import JaxLearner
    cfg = {"lr": 3e-4, "clip_param": 0.2, "vf_loss_coeff": 0.5,
           "entropy_coeff": 0.01, "minibatch_size": 16, "num_epochs": 1,
           "grad_clip": 0.5}
    learner = JaxLearner(cfg, obs_dim=4, action_dim=2)
    n = 64
    rng = np.random.default_rng(0)
    batch = {"obs": rng.standard_normal((n, 4)).astype(np.float32),
             "actions": rng.integers(0, 2, n),
             "logp": np.zeros(n, np.float32),
             "advantages": rng.standard_normal(n).astype(np.float32),
             "value_targets": rng.standard_normal(n).astype(np.float32)}
    m = learner.update_from_batch(batch)
    assert np.isfinite(m["total_loss"])
    assert learner.profiler.flops > 0
    assert learner.profiler.last["compute_ms"] > 0
    snap = _snap("runtime_rl_update")
    assert "runtime_rl_update_mfu" in snap


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PEAK_FLOPS", "123e9")
    assert profiling.detect_peak_flops() == 123e9
    monkeypatch.setenv("RAY_TPU_PEAK_BYTES_PER_S", "7e9")
    assert profiling.detect_peak_bytes_per_s() == 7e9
