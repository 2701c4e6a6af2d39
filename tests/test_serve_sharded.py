"""Multi-host (sharded) serve replicas, exercised end-to-end on CPU: one
replica = a 2-process gang joined into a single jax.distributed world
through the GCS-KV rendezvous, serving a value computed by an XLA
collective ACROSS the processes — so a correct answer proves the group
really runs as one SPMD world, not two copies (SURVEY §7.2 step 10;
reference replica lifecycle python/ray/serve/_private/deployment_state.py
has no multi-host analog — this is the TPU-native extension).

Same CI stand-in scheme as test_jax_distributed.py: CPU devices, Gloo-
backed collectives, identical code path to a real slice."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve

pytestmark = pytest.mark.slow


RAY_START = dict(num_cpus=8, object_store_memory=128 * 1024 * 1024)


class ShardedSum:
    """y = scale * x * sum(w) with w = [1..n_global_devices] sharded over
    every device of the GROUP's global mesh: the jnp.sum is a
    cross-process all-reduce, so each request's answer requires both
    ranks to participate."""

    def __init__(self, scale=1.0):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        assert jax.process_count() == 2, \
            f"sharded replica must span 2 processes, saw " \
            f"{jax.process_count()}"
        devs = np.array(jax.devices())
        self.n = len(devs)
        mesh = Mesh(devs, ("model",))
        dist = NamedSharding(mesh, P("model"))
        n_local = jax.local_device_count()
        rank = jax.process_index()
        local = np.arange(rank * n_local, (rank + 1) * n_local,
                          dtype=np.float32) + 1.0
        self.w = jax.make_array_from_process_local_data(
            dist, local, (self.n,))
        self.scale = float(scale)
        self._f = jax.jit(lambda x, w: x * jnp.sum(w),
                          out_shardings=NamedSharding(mesh, P()))

    def __call__(self, x):
        import jax
        y = self._f(np.float32(float(x) * self.scale), self.w)
        return float(jax.device_get(y))


def _expected(x, scale, n_devices=16):
    return scale * x * (n_devices * (n_devices + 1) / 2.0)


def test_sharded_replica_handle(ray_start):
    app = serve.deployment(ShardedSum, num_hosts=2,
                           ray_actor_options={"num_cpus": 0.5}).bind(1.0)
    handle = serve.run(app, name="sharded", route_prefix=None)
    got = handle.remote(2.0).result(timeout=120)
    assert got == pytest.approx(_expected(2.0, 1.0)), got
    # concurrent requests serialize through the SPMD lock but all answer
    results = [handle.remote(float(i)).result(timeout=120)
               for i in range(1, 4)]
    assert results == [pytest.approx(_expected(float(i), 1.0))
                       for i in range(1, 4)]
    serve.delete("sharded")


def test_sharded_replica_http_and_rolling_update(ray_start):
    """Serve a sharded model over HTTP, then roll to a new version while
    requests are in flight: zero dropped requests, every answer belongs
    to exactly one version, and the new version eventually serves."""
    app = serve.deployment(ShardedSum, num_hosts=2,
                           ray_actor_options={"num_cpus": 0.5}).bind(1.0)
    serve.run(app, name="shttp", route_prefix="/sharded",
              _http=True, http_port=18271)

    v1 = _expected(3.0, 1.0)
    v2 = _expected(3.0, 10.0)
    results, errors = [], []
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                req = urllib.request.Request(
                    "http://127.0.0.1:18271/sharded",
                    data=json.dumps(3.0).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as resp:
                    results.append(float(json.loads(resp.read())))
            except Exception as e:      # pragma: no cover - failure path
                errors.append(repr(e))
            time.sleep(0.05)

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        time.sleep(2.0)
        # rolling update: same app, new init arg — the controller surges
        # a NEW 2-process group, then drains and retires the old gang
        app2 = serve.deployment(
            ShardedSum, num_hosts=2,
            ray_actor_options={"num_cpus": 0.5}).bind(10.0)
        serve.run(app2, name="shttp", route_prefix="/sharded")
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if results and results[-1] == pytest.approx(v2):
                break
            time.sleep(0.5)
    finally:
        stop.set()
        t.join(timeout=150)
    assert not errors, f"dropped requests during roll: {errors[:5]}"
    assert results, "no responses recorded"
    assert results[-1] == pytest.approx(v2), results[-5:]
    for r in results:
        assert r == pytest.approx(v1) or r == pytest.approx(v2), r
    serve.delete("shttp")


class ShardedStreamer(ShardedSum):
    """Token-streaming shape: each yielded chunk is one SPMD step (the
    jitted cross-process all-reduce), so a correct stream proves the
    ranks advance their generators in lockstep."""

    def stream(self, x):
        import jax
        for i in range(5):
            y = self._f(np.float32(float(x) + i), self.w)
            yield float(jax.device_get(y))


def test_sharded_replica_streaming(ray_start):
    """Streamed responses from a sharded gang: rank 0 yields per-step
    SPMD results; every chunk must be present, ordered, and correct."""
    app = serve.deployment(ShardedStreamer, num_hosts=2,
                           ray_actor_options={"num_cpus": 0.5}).bind(1.0)
    handle = serve.run(app, name="sstream", route_prefix=None)
    gen = handle.options(stream=True).stream.remote(2.0)
    got = [chunk for chunk in gen]
    assert got == [pytest.approx(_expected(2.0 + i, 1.0))
                   for i in range(5)], got
    # a second stream after the first completes (SPMD lock released)
    gen = handle.options(stream=True).stream.remote(0.0)
    assert [c for c in gen] == [pytest.approx(_expected(float(i), 1.0))
                                for i in range(5)]
    serve.delete("sstream")


def test_sharded_autoscaling_gangs(ray_start):
    """Autoscaling where one replica = one GANG: sustained queue depth
    on the single gang (SPMD lock serializes requests) upscales to a
    second 2-process gang; idling back down retires a whole gang."""

    class SlowShardedSum(ShardedSum):
        def __call__(self, x):
            import time as _t
            _t.sleep(0.3)       # hold the SPMD slot: queue builds
            return super().__call__(x)

    from ray_tpu.serve.api import _get_controller
    app = serve.deployment(
        SlowShardedSum, num_hosts=2,
        ray_actor_options={"num_cpus": 0.25},
        autoscaling_config={"min_replicas": 1, "max_replicas": 2,
                            "target_ongoing_requests": 1.0,
                            "upscale_delay_s": 1.0,
                            "downscale_delay_s": 4.0,
                            "look_back_period_s": 4.0},
    ).bind(1.0)
    handle = serve.run(app, name="sauto", route_prefix=None)
    ctrl = _get_controller()

    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                handle.remote(1.0).result(timeout=120)
            except Exception:
                pass

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 120
        scaled_up = False
        while time.monotonic() < deadline:
            st = ray_tpu.get(ctrl.get_status.remote(), timeout=30)
            if st["sauto"]["SlowShardedSum"]["running"] >= 2:
                scaled_up = True
                break
            time.sleep(1.0)
        assert scaled_up, "never scaled to a second gang"
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=130)
    # idle: a whole gang drains away back to min_replicas
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        st = ray_tpu.get(ctrl.get_status.remote(), timeout=30)
        if st["sauto"]["SlowShardedSum"]["running"] == 1:
            break
        time.sleep(1.0)
    assert st["sauto"]["SlowShardedSum"]["running"] == 1, st
    # the survivor still serves
    assert handle.remote(2.0).result(timeout=120) == \
        pytest.approx(_expected(2.0, 1.0))
    serve.delete("sauto")


def test_sharded_group_torn_down_with_app(ray_start):
    """Deleting the app kills every rank of the gang and releases its
    placement group — no orphaned shard actors or bundles."""
    from ray_tpu.serve.api import _get_controller

    app = serve.deployment(ShardedSum, num_hosts=2,
                           ray_actor_options={"num_cpus": 0.5}).bind(1.0)
    handle = serve.run(app, name="stear", route_prefix=None)
    assert handle.remote(1.0).result(timeout=120) == \
        pytest.approx(_expected(1.0, 1.0))
    ctrl = _get_controller()
    info = ray_tpu.get(
        ctrl.get_deployment_info.remote("stear", "ShardedSum"), timeout=30)
    (rank0,) = info["replicas"]
    serve.delete("stear")
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            ray_tpu.get(rank0.get_queue_len.remote(), timeout=5)
            time.sleep(0.5)
        except ray_tpu.ActorDiedError:
            break
    else:
        pytest.fail("rank-0 shard still alive after app delete")
