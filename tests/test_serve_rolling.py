"""Rolling redeploys (reference: serve _private/deployment_state.py —
code/config changes replace replicas GRADUALLY, surging new-version
replicas before retiring old ones, so capacity never drops to zero)."""

import time

import ray_tpu
from ray_tpu import serve


RAY_START = dict(num_cpus=4, object_store_memory=128 * 1024 * 1024)


@serve.deployment(num_replicas=2)
class Tagged:
    def __init__(self, tag):
        self.tag = tag

    def __call__(self, payload):
        return self.tag


def test_rolling_update_no_downtime(ray_start):
    handle = serve.run(Tagged.bind("v1"), name="roll")
    assert handle.remote("x").result(timeout=60) == "v1"

    # redeploy with new code/config -> rolling replacement
    handle = serve.run(Tagged.bind("v2"), name="roll")

    # during the roll EVERY request must succeed (old or new version);
    # eventually only v2 answers
    deadline = time.time() + 120
    seen = set()
    consecutive_v2 = 0
    while time.time() < deadline:
        tags = [handle.remote("x").result(timeout=30) for _ in range(6)]
        seen.update(tags)
        consecutive_v2 = consecutive_v2 + 1 if set(tags) == {"v2"} else 0
        if consecutive_v2 >= 3:    # roll definitely finished
            break
        time.sleep(1.0)
    assert consecutive_v2 >= 3, f"never converged to v2: {seen}"
    # steady state
    for _ in range(4):
        assert handle.remote("x").result(timeout=30) == "v2"
