"""Preemption-tolerant serving: burn-rate autoscaling loop, drain
semantics, and resumable streams (ROADMAP item 2, control-loop half).

Hermetic tier (no cluster, any interpreter):
- BurnRateScaler policy: sustained dual-window burn raises the replica
  target within two slow windows, an instant spike does not, idle
  replicas release after cooldown (driven against a REAL GcsServer
  metrics ring with a fake clock — the same synthetic-push harness the
  SLO tests use).
- Controller drain-deadline semantics with monkeypatched ray_tpu
  primitives: queue empties -> reaped clean; deadline expiry -> forced
  kill; a draining replica never reappears in routing tables.
- Scheduler/engine drain mode; LLMDeployment resume_tokens continuation
  (greedy-exact); the handle-side stream re-route state machine.
- Autoscaler escalating backoff + serve replica-demand export.

Cluster tier (Python >= 3.12): notice-based preemption end to end and
stream resume across a real replica kill.
"""

import itertools
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve


# --------------------------------------------------------------------------
# fakes: replica handles + ray primitives for lock-step controller tests
# --------------------------------------------------------------------------

class _FakeRef:
    _ids = itertools.count()

    def __init__(self, resolve):
        self.id = f"fakeref-{next(self._ids)}"
        self._resolve = resolve      # () -> value, may raise


class _FakeMethod:
    def __init__(self, fn):
        self._fn = fn

    def remote(self, *a, **kw):
        return _FakeRef(lambda: self._fn(*a, **kw))


class _FakeReplica:
    def __init__(self, queue_len=0, dead=False):
        self.queue_len = queue_len
        self.dead = dead
        self.drain_notices = 0

    def __getattr__(self, name):
        if name == "get_queue_len":
            return _FakeMethod(self._qlen)
        if name == "get_runtime_state":
            return _FakeMethod(
                lambda: {"queue_len": self._qlen(), "draining": False})
        if name == "begin_drain":
            return _FakeMethod(self._begin_drain)
        if name == "check_health":
            return _FakeMethod(lambda: True)
        raise AttributeError(name)

    def _qlen(self):
        if self.dead:
            raise ray_tpu.ActorDiedError("fake replica dead")
        return self.queue_len

    def _begin_drain(self):
        self.drain_notices += 1
        return True


@pytest.fixture
def fake_ray(monkeypatch):
    """Route the controller's ray_tpu.get/wait/kill through _FakeRefs."""
    killed = []

    def fake_get(obj, timeout=None):
        if isinstance(obj, list):
            return [fake_get(o, timeout=timeout) for o in obj]
        return obj._resolve()

    def fake_wait(refs, num_returns=None, timeout=None):
        return list(refs), []

    monkeypatch.setattr(ray_tpu, "get", fake_get)
    monkeypatch.setattr(ray_tpu, "wait", fake_wait)
    monkeypatch.setattr(ray_tpu, "kill", killed.append)
    return killed


@pytest.fixture
def ctrl():
    from ray_tpu.serve.controller import ServeController

    class _QuietController(ServeController):
        def _reconcile_loop(self):   # tests drive ticks by hand
            return

    c = _QuietController()
    c._stop = True
    return c


def _mk_dep(replicas, config=None, target=None):
    cfg = {"num_replicas": len(replicas),
           "graceful_shutdown_timeout_s": 5.0,
           "preempt_grace_s": 2.0,
           "resumable_streams": False}
    cfg.update(config or {})
    return {"spec": {"name": "d", "app_name": "a", "config": cfg},
            "replicas": list(replicas), "version": 0,
            "target": len(replicas) if target is None else target,
            "replica_gens": [0] * len(replicas), "gen": 0,
            # park replica construction: hermetic tests never build
            # real actors, the flag keeps _reconcile_deployment quiet
            "_creating": True}


# --------------------------------------------------------------------------
# controller drain-deadline semantics (satellite: drain tests)
# --------------------------------------------------------------------------

def test_drain_reaps_clean_once_queue_empties(ctrl, fake_ray):
    r = _FakeReplica(queue_len=2)
    dep = _mk_dep([r])
    ctrl.apps = {"a": {"d": dep}}
    with ctrl._lock:
        assert ctrl._detach_for_drain(dep, r, grace_s=30.0)
    ctrl._reap_draining(dep)
    assert fake_ray == [] and len(dep["draining"]) == 1  # busy: kept
    r.queue_len = 0
    ctrl._reap_draining(dep)
    assert fake_ray == [r]                # queue empty -> reaped clean
    assert dep["draining"] == []


def test_drain_deadline_expiry_forces_kill(ctrl, fake_ray):
    r = _FakeReplica(queue_len=3)         # never drains
    dep = _mk_dep([r])
    ctrl.apps = {"a": {"d": dep}}
    with ctrl._lock:
        ctrl._detach_for_drain(dep, r, grace_s=0.05)
    time.sleep(0.06)
    ctrl._reap_draining(dep)
    assert fake_ray == [r]                # forced kill at the deadline
    assert dep["draining"] == []


def test_dead_draining_replica_reaped_immediately(ctrl, fake_ray):
    r = _FakeReplica(queue_len=1, dead=True)
    dep = _mk_dep([r])
    ctrl.apps = {"a": {"d": dep}}
    with ctrl._lock:
        ctrl._detach_for_drain(dep, r, grace_s=60.0)
    ctrl._reap_draining(dep)
    assert fake_ray == [r]


def test_draining_replica_never_in_routing_tables(ctrl, fake_ray):
    r1, r2 = _FakeReplica(queue_len=1), _FakeReplica()
    dep = _mk_dep([r1, r2])
    ctrl.apps = {"a": {"d": dep}}
    v0 = ctrl.get_deployment_info("a", "d")["version"]
    assert ctrl.preempt_replica("a", "d", 0, grace_s=10.0)
    assert r1.drain_notices == 1          # the notice reached the replica
    info = ctrl.get_deployment_info("a", "d")
    assert info["version"] > v0           # routers are woken
    assert r1 not in info["replicas"] and r2 in info["replicas"]
    # and it stays out: subsequent tables are built from dep["replicas"]
    assert r1 not in ctrl.get_deployment_info("a", "d")["replicas"]
    assert [h for h, _ in dep["draining"]] == [r1]
    # preempting the LAST replica still detaches it (capacity dips until
    # the pre-started replacement lands — routing never sees the corpse)
    assert ctrl.preempt_replica("a", "d", 0, grace_s=10.0)
    assert ctrl.get_deployment_info("a", "d")["replicas"] == []


def test_probe_states_picks_up_self_draining_replica(ctrl, fake_ray):
    """A replica that flipped ITSELF into draining (metadata notice) is
    detached on the next reconcile tick."""
    r1, r2 = _FakeReplica(), _FakeReplica()
    dep = _mk_dep([r1, r2])
    ctrl.apps = {"a": {"d": dep}}
    probed, states = ctrl._probe_states(dep)
    assert [s["draining"] for s in states] == [False, False]
    states[0]["draining"] = True          # as the probe would report
    with ctrl._lock:
        for r, s in zip(probed, states):
            if s.get("draining"):
                ctrl._detach_for_drain(dep, r, ctrl._preempt_grace(dep))
    assert r1 not in dep["replicas"] and r2 in dep["replicas"]
    assert ctrl._preempt_grace(dep) == 2.0


# --------------------------------------------------------------------------
# burn-rate autoscaling (tentpole a)
# --------------------------------------------------------------------------

_AUTO = {"min_replicas": 1, "max_replicas": 4,
         "target_ongoing_requests": 2.0,
         "burn_upscale_hold_s": 6.0, "burn_downscale_idle_s": 60.0,
         "burn_cooldown_s": 30.0, "burn_release_threshold": 0.25}


def _rows(violating, fast=0.0, slow=0.0):
    return [{"objective": "latency", "violating": violating,
             "burn_fast": fast, "burn_slow": slow}]


def test_burn_scaler_requires_sustained_violation():
    from ray_tpu.serve.slo import BurnRateScaler
    s = BurnRateScaler()
    # one violating tick (an instant spike the multiwindow rule let
    # through) never scales: the hold gate needs 6s of it
    assert s.decide(_AUTO, _rows(True, 3.0, 1.5), 1, 0.0, now=0.0) == 1
    assert s.decide(_AUTO, _rows(False), 1, 0.0, now=2.0) == 1
    assert s.decide(_AUTO, _rows(True, 3.0, 1.5), 1, 0.0, now=4.0) == 1
    # sustained violation: hold elapses -> scale, proportional to burn
    assert s.decide(_AUTO, _rows(True, 3.0, 1.5), 1, 8.0, now=8.0) == 1
    assert s.decide(_AUTO, _rows(True, 3.0, 1.5), 1, 8.0, now=10.1) == 2
    # cooldown: still violating but no second action yet
    assert s.decide(_AUTO, _rows(True, 3.0, 1.5), 2, 8.0, now=20.0) == 2
    # past cooldown AND still sustained: next step (2 * burn 1.5 -> 3)
    assert s.decide(_AUTO, _rows(True, 3.0, 1.5), 2, 8.0, now=41.0) == 3
    # never exceeds max_replicas
    assert s.decide(_AUTO, _rows(True, 9.0, 9.0), 4, 8.0, now=100.0) == 4


def test_burn_scaler_releases_idle_after_cooldown():
    from ray_tpu.serve.slo import BurnRateScaler
    s = BurnRateScaler()
    # burn quiet + load low, but not for long enough: no release
    assert s.decide(_AUTO, _rows(False, 0.0, 0.0), 3, 0.0, now=0.0) == 3
    assert s.decide(_AUTO, _rows(False, 0.0, 0.0), 3, 0.0, now=30.0) == 3
    # idle hold (60s) elapsed -> one step down
    assert s.decide(_AUTO, _rows(False, 0.0, 0.0), 3, 0.0, now=61.0) == 2
    # cooldown separates release steps
    assert s.decide(_AUTO, _rows(False, 0.0, 0.0), 2, 0.0, now=80.0) == 2
    assert s.decide(_AUTO, _rows(False, 0.0, 0.0), 2, 0.0, now=125.0) == 1
    # floor at min_replicas
    assert s.decide(_AUTO, _rows(False, 0.0, 0.0), 1, 0.0, now=300.0) == 1


def test_burn_scaler_loaded_fleet_does_not_release():
    from ray_tpu.serve.slo import BurnRateScaler
    s = BurnRateScaler()
    # burn is quiet but per-replica load is healthy: keep capacity
    for t in range(0, 200, 2):
        assert s.decide(_AUTO, _rows(False, 0.1, 0.1), 3, 5.0,
                        now=float(t)) == 3


def test_burn_scaler_against_metrics_ring_two_slow_windows():
    """Acceptance (hermetic, fake metrics ring = a real GcsServer fed
    synthetic pushes + a fake clock): sustained dual-window burn raises
    the target within two slow windows; an instant spike lights only
    the fast window and never scales; idle releases after cooldown."""
    from ray_tpu._private.gcs import GcsServer
    from ray_tpu.serve.slo import BurnRateScaler, evaluate_slo
    from ray_tpu.util.metrics import Histogram

    slo = {"p95_ttft_ms": 200.0, "latency_metric": "churn_ttft_ms",
           "fast_window_s": 30.0, "slow_window_s": 120.0}
    auto = dict(_AUTO, burn_upscale_hold_s=4.0, burn_cooldown_s=20.0,
                burn_downscale_idle_s=40.0)

    g = GcsServer()
    h = Histogram("churn_ttft_ms",
                  boundaries=[10, 50, 100, 250, 500, 1000, 2500])
    clock = {"now": 1000.0}

    def query(metric, window=60.0, agg="avg", tags=None, threshold=None):
        return g.h_query_metrics(None, metric, window=window, agg=agg,
                                 tags=tags, threshold=threshold,
                                 now=clock["now"])

    def tick(ttft_ms, n_obs=20):
        for _ in range(n_obs):
            h.observe(ttft_ms)
        g.h_report_metrics(None, "w1", [h._snapshot()], ts=clock["now"])
        clock["now"] += 2.0
        return evaluate_slo(slo, query)

    scaler = BurnRateScaler()
    target = 1

    # healthy baseline fills both windows
    for _ in range(60):
        rows = tick(40.0)
        target = scaler.decide(auto, rows, target, 2.0, clock["now"])
    assert target == 1

    # instant spike: one bad push -> fast window burns, slow does not,
    # the multiwindow rule keeps violating False and the target flat
    rows = tick(800.0)
    assert rows[0]["burn_fast"] > 1.0 and not rows[0]["violating"]
    target = scaler.decide(auto, rows, target, 2.0, clock["now"])
    for _ in range(20):
        rows = tick(40.0)
        target = scaler.decide(auto, rows, target, 2.0, clock["now"])
    assert target == 1

    # sustained burn: every request blows the objective; the target must
    # rise within two slow windows (240 simulated seconds)
    t_bad_start = clock["now"]
    raised_at = None
    for _ in range(150):
        rows = tick(800.0)
        target = scaler.decide(auto, rows, target, 9.0, clock["now"])
        if target > 1:
            raised_at = clock["now"]
            break
    assert raised_at is not None, "sustained burn never scaled up"
    assert raised_at - t_bad_start <= 2 * 120.0
    assert rows[0]["violating"]

    # recovery + idle: traffic stops blowing the objective and the load
    # drops. The burn keeps both windows lit for a while (the scaler may
    # even step up once more — correct: the SLO is still burning), then
    # the windows drain, the idle hold elapses, and the fleet releases
    # all the way back to min_replicas, one replica per cooldown.
    released_at = None
    for _ in range(400):
        rows = tick(30.0)
        new_target = scaler.decide(auto, rows, target, 0.0, clock["now"])
        if new_target < target:
            released_at = clock["now"]
        target = new_target
        if target == 1 and released_at is not None:
            break
    assert released_at is not None, "idle replicas never released"
    assert target == 1


def test_controller_burn_autoscale_and_demand_export(ctrl):
    dep = _mk_dep([_FakeReplica()],
                  config={"autoscaling_config": dict(
                      _AUTO, burn_upscale_hold_s=0.0, burn_cooldown_s=0.0),
                      "ray_actor_options": {"num_cpus": 1.0,
                                            "num_tpus": 4.0}})
    ctrl.apps = {"a": {"d": dep}}
    with ctrl._lock:
        ctrl._burn_autoscale("a", "d", dep,
                             _rows(True, 3.0, 2.0), [1])
    assert dep["target"] == 2
    # the raised target exports as replica demand for the cluster
    # autoscaler (deficit = target - running = 1)
    demand = ctrl.get_replica_demand()
    assert demand == [{"CPU": 1.0, "TPU": 4.0}]
    # no slo rows (deployment without slo_config) -> no scaling
    with ctrl._lock:
        ctrl._burn_autoscale("a", "d", dep, None, [1])
    assert dep["target"] == 2


# --------------------------------------------------------------------------
# autoscaler: serve demand + escalating backoff (satellites)
# --------------------------------------------------------------------------

class _RecordingProvider:
    def __init__(self):
        self.created = []

    def create_node(self, node_type, resources, labels):
        nid = f"prov-{len(self.created)}"
        self.created.append(node_type)
        return nid

    def terminate_node(self, provider_node_id):
        pass

    def non_terminated_nodes(self):
        return [f"prov-{i}" for i in range(len(self.created))]


def _head_node(pending=None):
    return [{"node_id": "head", "alive": True,
             "total": {"CPU": 1.0}, "available": {"CPU": 0.0},
             "pending_demand": list(pending or [])}]


def test_autoscaler_acquires_nodes_for_serve_replica_demand():
    from ray_tpu.autoscaler.autoscaler import (Autoscaler,
                                               AutoscalerConfig,
                                               NodeTypeConfig)
    provider = _RecordingProvider()
    cfg = AutoscalerConfig(
        node_types={"tpu-host": NodeTypeConfig(
            resources={"CPU": 1.0, "TPU": 4.0}, max_workers=4)})
    demand = [{"CPU": 1.0, "TPU": 4.0}, {"CPU": 1.0, "TPU": 4.0}]
    a = Autoscaler(cfg, provider, nodes_fn=_head_node,
                   serve_demand_fn=lambda: demand)
    actions = a.step()
    # two missing replicas, one TPU host each
    assert actions["launched"] == ["tpu-host", "tpu-host"]
    # in-flight launches absorb the same demand next step: no relaunch
    assert a.step()["launched"] == []


def test_serve_demand_dedupes_against_lease_demand():
    from ray_tpu.autoscaler.autoscaler import (Autoscaler,
                                               AutoscalerConfig,
                                               NodeTypeConfig)
    provider = _RecordingProvider()
    cfg = AutoscalerConfig(
        node_types={"tpu-host": NodeTypeConfig(
            resources={"CPU": 1.0, "TPU": 4.0}, max_workers=4)})
    req = {"CPU": 1.0, "TPU": 4.0}
    a = Autoscaler(cfg, provider,
                   nodes_fn=lambda: _head_node(pending=[dict(req)]),
                   serve_demand_fn=lambda: [dict(req)])
    # the replica's lease already shows as pending node demand: one
    # launch, not two
    assert a.step()["launched"] == ["tpu-host"]


def test_serve_demand_failure_never_fails_step():
    from ray_tpu.autoscaler.autoscaler import (Autoscaler,
                                               AutoscalerConfig)

    def boom():
        raise RuntimeError("controller gone")

    a = Autoscaler(AutoscalerConfig(node_types={}), _RecordingProvider(),
                   nodes_fn=_head_node, serve_demand_fn=boom)
    assert a.step()["launched"] == []


def test_autoscaler_backoff_escalates_and_caps():
    from ray_tpu.autoscaler.autoscaler import Autoscaler, AutoscalerConfig
    cfg = AutoscalerConfig(node_types={}, upscale_interval_s=2.0,
                           max_backoff_s=30.0)
    a = Autoscaler(cfg, _RecordingProvider(), nodes_fn=_head_node)
    assert a._step_delay(0) == 2.0
    assert a._step_delay(1) == 4.0
    assert a._step_delay(2) == 8.0
    assert a._step_delay(4) == 30.0       # capped
    assert a._step_delay(50) == 30.0      # and never overflows


def test_autoscaler_run_counts_failures_and_backs_off():
    from ray_tpu.autoscaler.autoscaler import Autoscaler, AutoscalerConfig
    from ray_tpu.util import metrics as metrics_mod

    calls = []

    def bad_nodes():
        calls.append(time.monotonic())
        raise RuntimeError("gcs down")

    cfg = AutoscalerConfig(node_types={}, upscale_interval_s=0.01,
                           max_backoff_s=0.05)
    a = Autoscaler(cfg, _RecordingProvider(), nodes_fn=bad_nodes)

    def counter_value():
        for m in metrics_mod.registry_snapshot():
            if m["name"] == "autoscaler_step_failures":
                return sum(v for _, v in m["samples"])
        return 0.0

    before = counter_value()
    stop = threading.Event()
    th = threading.Thread(target=a.run, args=(stop,), daemon=True)
    th.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and len(calls) < 5:
        time.sleep(0.01)
    stop.set()
    th.join(timeout=5)
    assert len(calls) >= 5
    assert a._consecutive_failures >= 5
    assert counter_value() - before >= 5
    # consecutive failures spaced out: later gaps reach the cap instead
    # of hot-looping at the base interval
    gaps = [b - a_ for a_, b in zip(calls, calls[1:])]
    assert max(gaps) >= 0.04


# --------------------------------------------------------------------------
# scheduler / engine drain mode (tentpole b: admission stops)
# --------------------------------------------------------------------------

def test_scheduler_drain_mode_refuses_new_finishes_queued():
    from ray_tpu.inference.scheduler import Request, Scheduler
    s = Scheduler(n_slots=2, prefill_budget=8, chunk_size=4)
    h1 = s.submit(Request(tokens=[1, 2, 3], max_new_tokens=4))
    s.begin_drain()
    assert s.draining and not s.drained()
    with pytest.raises(RuntimeError, match="draining"):
        s.submit(Request(tokens=[4, 5], max_new_tokens=4))
    # the already-queued request still admits and runs to completion
    chunks = s.plan_prefill()
    assert chunks and chunks[0].state.handle is h1
    s.prefill_done(chunks[0].state, first_token=7, now=time.monotonic())
    st = chunks[0].state
    for tok in (8, 9, 10):
        s.decode_emit(st, tok, time.monotonic())
    assert h1.finish_reason is None         # decided, held for deliver()
    s.deliver()
    assert h1.tokens() == [7, 8, 9, 10]
    assert s.drained()


def _tiny_llm_config():
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=512, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)


def test_engine_drain_finishes_inflight_and_refuses_new():
    from ray_tpu.inference import LLMDeployment
    dep = LLMDeployment(_tiny_llm_config(), n_slots=2, max_len=256,
                        prefill_chunk=8, prefill_budget=16)
    try:
        gen = dep([1, 2, 3, 4], max_new_tokens=8)
        # direct calls yield coalesced chunks (the first is the eager
        # single-token flush); flatten for token counting
        got = [next(gen), next(gen)]
        dep.begin_drain()
        assert dep.drain_status()["draining"]
        with pytest.raises(RuntimeError, match="draining"):
            dep.engine.submit([5, 6], max_new_tokens=4)
        got.extend(gen)                   # in-flight stream completes
        assert sum(len(c) for c in got) == 8
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if dep.drain_status()["pending"] == 0:
                break
            time.sleep(0.02)
        assert dep.drain_status() == {"draining": True, "pending": 0}
    finally:
        dep.engine.stop()


def test_llm_resume_tokens_continue_exactly():
    """The resume contract: prompt + delivered tokens re-prefill (one
    chunked admission) and the continuation is greedy-identical to the
    uninterrupted stream — the exactly-once guarantee the handle's
    re-route depends on."""
    from ray_tpu.inference import LLMDeployment
    assert LLMDeployment.__serve_resumable__
    dep = LLMDeployment(_tiny_llm_config(), n_slots=2, max_len=256,
                        prefill_chunk=8, prefill_budget=16)
    try:
        full = dep.generate([1, 2, 3, 4], max_new_tokens=12)
        assert len(full) == 12
        resumed = [t for chunk in dep([1, 2, 3, 4], max_new_tokens=12,
                                      resume_tokens=full[:5])
                   for t in chunk]       # flatten coalesced chunks
        assert resumed == full[5:]
        # everything already delivered -> empty continuation, no slot
        assert list(dep([1, 2, 3, 4], max_new_tokens=12,
                        resume_tokens=full)) == []
    finally:
        dep.engine.stop()


# --------------------------------------------------------------------------
# handle: streaming re-route / resume state machine (satellite 1)
# --------------------------------------------------------------------------

class _StubGen:
    """Stands in for the core ObjectRefGenerator: yields canned items,
    then optionally dies."""

    def __init__(self, items, fail_after=None, error=None):
        self._items = list(items)
        self._i = 0
        self._fail_after = fail_after
        self._error = error
        self.closed = False

    def next(self, timeout=None):
        if self._fail_after is not None and self._i >= self._fail_after:
            raise self._error
        if self._i >= len(self._items):
            raise StopIteration
        v = self._items[self._i]
        self._i += 1
        return v

    def close(self):
        self.closed = True


def _wrap(stub, **kw):
    from ray_tpu.serve.handle import DeploymentResponseGenerator
    g = DeploymentResponseGenerator(stub, None, 0, **kw)
    g._get = lambda ref: ref      # stub items are already values
    return g


def test_stream_resume_resumable_continues_with_delivered_chunks():
    seen = {}

    def resume(delivered, chunks):
        seen["delivered"] = delivered
        seen["chunks"] = list(chunks)
        return _wrap(_StubGen([2, 3, 4])), 0

    g = _wrap(_StubGen([0, 1], fail_after=2,
                       error=ray_tpu.ActorDiedError("replica gone")),
              resume=resume, record_chunks=True)
    assert list(g) == [0, 1, 2, 3, 4]
    assert seen == {"delivered": 2, "chunks": [0, 1]}


def test_stream_resume_nonresumable_skips_delivered_chunks():
    def resume(delivered, chunks):
        assert chunks is None         # non-resumable: count-only dedupe
        return _wrap(_StubGen([0, 1, 2, 3, 4])), delivered

    g = _wrap(_StubGen([0, 1, 2], fail_after=3,
                       error=ray_tpu.ActorDiedError("replica gone")),
              resume=resume)
    # restart re-produces everything; the wrapper drops the 3 duplicates
    assert list(g) == [0, 1, 2, 3, 4]


def test_stream_resume_is_one_shot():
    def resume(delivered, chunks):
        return _wrap(_StubGen([1], fail_after=1,
                              error=ray_tpu.ActorDiedError("again"))), 0

    g = _wrap(_StubGen([0], fail_after=1,
                       error=ray_tpu.ActorDiedError("first")),
              resume=resume)
    assert next(g) == 0
    assert next(g) == 1
    with pytest.raises(ray_tpu.ActorDiedError):
        next(g)                       # second death: no second resume


def test_stream_app_errors_do_not_trigger_resume():
    def resume(delivered, chunks):
        raise AssertionError("must not re-route an application error")

    g = _wrap(_StubGen([0], fail_after=1, error=ValueError("user bug")),
              resume=resume)
    assert next(g) == 0
    with pytest.raises(ValueError, match="user bug"):
        next(g)


def test_stream_resume_surfaces_original_death_when_retry_fails():
    def resume(delivered, chunks):
        raise RuntimeError("no replicas")

    g = _wrap(_StubGen([], fail_after=0,
                       error=ray_tpu.ActorDiedError("original")),
              resume=resume)
    with pytest.raises(ray_tpu.ActorDiedError, match="original"):
        next(g)


# --------------------------------------------------------------------------
# preemption notice channel (tpu.py + replica watch)
# --------------------------------------------------------------------------

def test_check_preemption_notice_env_and_file(tmp_path, monkeypatch):
    from ray_tpu._private.accelerators import tpu as tpu_accel
    monkeypatch.delenv(tpu_accel.PREEMPT_TEST_ENV, raising=False)
    monkeypatch.delenv(tpu_accel.PREEMPT_TEST_FILE_ENV, raising=False)
    monkeypatch.setenv("RAY_TPU_DISABLE_GCE_METADATA", "1")
    assert not tpu_accel.check_preemption_notice()
    assert not tpu_accel.preemption_watch_enabled()
    marker = tmp_path / "preempt-notice"
    monkeypatch.setenv(tpu_accel.PREEMPT_TEST_FILE_ENV, str(marker))
    assert tpu_accel.preemption_watch_enabled()
    assert not tpu_accel.check_preemption_notice()
    marker.touch()
    assert tpu_accel.check_preemption_notice()
    monkeypatch.delenv(tpu_accel.PREEMPT_TEST_FILE_ENV)
    monkeypatch.setenv(tpu_accel.PREEMPT_TEST_ENV, "1")
    assert tpu_accel.check_preemption_notice()


class _DrainTracker:
    def __init__(self):
        self.drained = 0

    def __call__(self, x):
        return x

    def begin_drain(self):
        self.drained += 1

    def state(self):
        return self.drained


def test_replica_preemption_file_flips_draining(tmp_path, monkeypatch):
    import cloudpickle

    from ray_tpu.serve.replica import Replica
    marker = tmp_path / "preempt-notice"
    monkeypatch.setenv("RAY_TPU_TESTING_PREEMPT_FILE", str(marker))
    monkeypatch.setenv("RAY_TPU_PREEMPT_POLL_S", "0.02")
    r = Replica(cloudpickle.dumps(_DrainTracker), (), {}, False)
    assert r.get_runtime_state() == {"queue_len": 0, "draining": False}
    marker.touch()                    # the "notice" arrives
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if r.get_runtime_state()["draining"]:
            break
        time.sleep(0.02)
    assert r.get_runtime_state()["draining"]
    # the replica boundary now refuses new admissions (router-staleness
    # window) so the handle layer re-routes instead of erroring out
    from ray_tpu.serve.replica import ReplicaDrainingError
    with pytest.raises(ReplicaDrainingError):
        r.handle_request("state", (), {})
    # the notice reached the user callable exactly once (idempotent)
    assert r._callable.state() == 1
    r.begin_drain()
    assert r._callable.state() == 1


# --------------------------------------------------------------------------
# cluster tier: the real lifecycle (notice -> drain -> replace -> resume)
# --------------------------------------------------------------------------

RAY_START = dict(num_cpus=6)


def test_preempt_one_drains_replaces_with_zero_errors(ray_start):
    """Notice-based preemption: the in-flight stream completes on the
    draining replica, new requests land on the replacement, the client
    sees zero errors."""
    from ray_tpu.inference import LLMDeployment
    from ray_tpu.util.chaos import ServeReplicaKiller
    dep = serve.deployment(LLMDeployment, preempt_grace_s=30.0)
    serve.run(dep.bind(_tiny_llm_config(), n_slots=2, max_len=512,
                       prefill_chunk=8, prefill_budget=16),
              name="llm-preempt")
    h = serve.get_app_handle("llm-preempt")
    expected = list(h.options(stream=True).remote([1, 2, 3],
                                                  max_new_tokens=24))
    gen = h.options(stream=True).remote([1, 2, 3], max_new_tokens=24)
    got = [next(gen) for _ in range(3)]
    killer = ServeReplicaKiller("llm-preempt", "LLMDeployment")
    assert killer.preempt_one()
    got.extend(gen)                   # drained replica finishes the stream
    assert got == expected
    assert killer.wait_for_replacement(timeout_s=90, handle=h)
    # replacement serves new load; the drained replica is gone from the
    # routing table so nothing routes to the corpse
    assert list(h.options(stream=True).remote([1, 2, 3],
                                              max_new_tokens=24)) \
        == expected
    serve.delete("llm-preempt")


def test_stream_resumes_on_survivor_after_kill(ray_start):
    """Hard replica death mid-stream: the handle resubmits with
    resume_tokens and the client sees the exact greedy continuation —
    zero dropped, zero duplicated tokens."""
    from ray_tpu.inference import LLMDeployment
    from ray_tpu.util.chaos import ServeReplicaKiller
    dep = serve.deployment(LLMDeployment, num_replicas=2)
    serve.run(dep.bind(_tiny_llm_config(), n_slots=2, max_len=512,
                       prefill_chunk=8, prefill_budget=16),
              name="llm-resume")
    h = serve.get_app_handle("llm-resume")
    expected = list(h.options(stream=True).remote([5, 6, 7],
                                                  max_new_tokens=32))
    assert len(expected) == 32
    killer = ServeReplicaKiller("llm-resume", "LLMDeployment")
    gen = h.options(stream=True).remote([5, 6, 7], max_new_tokens=32)
    got = [next(gen) for _ in range(4)]
    assert killer.kill_one(prefer_busy=True)
    got.extend(gen)                   # resumes on the survivor
    assert got == expected
    assert killer.wait_for_replacement(timeout_s=90, min_running=2,
                                       handle=h)
    serve.delete("llm-resume")
