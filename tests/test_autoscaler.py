"""Autoscaler loop against the fake provider: unmet demand launches real
local nodes; idle launched nodes terminate (reference hermetic pattern:
python/ray/tests/autoscaler + FakeMultiNodeProvider)."""

import time

import ray_tpu
from ray_tpu.autoscaler import (Autoscaler, AutoscalerConfig,
                                FakeMultiNodeProvider)
from ray_tpu.autoscaler.autoscaler import NodeTypeConfig


RAY_START = dict(num_cpus=1, object_store_memory=64 * 1024 * 1024)


def test_scale_up_then_down(ray_start):
    provider = FakeMultiNodeProvider(ray_tpu.get_gcs_address())
    config = AutoscalerConfig(
        node_types={"cpu4": NodeTypeConfig(resources={"CPU": 4.0},
                                           max_workers=2)},
        idle_timeout_s=4.0)
    scaler = Autoscaler(config, provider)

    @ray_tpu.remote(num_cpus=2)
    def big():
        import time
        time.sleep(3)
        return 1

    # 1-CPU head can't run a 2-CPU task: demand appears in heartbeats
    ref = big.remote()
    launched = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not launched:
        time.sleep(1.0)
        launched = scaler.step()["launched"]
    assert launched == ["cpu4"]
    assert ray_tpu.get(ref, timeout=60) == 1

    # idle node terminates after the timeout
    deadline = time.monotonic() + 40
    terminated = []
    while time.monotonic() < deadline and not terminated:
        time.sleep(1.0)
        terminated = scaler.step()["terminated"]
    assert terminated
    assert provider.non_terminated_nodes() == []
