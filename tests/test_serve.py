"""Serve tests: deployments, scaling, composition, batching, HTTP ingress
(reference: python/ray/serve/tests/ shapes — controller+replicas on a local
cluster, hit over handle and HTTP)."""

import time

import ray_tpu
from ray_tpu import serve


RAY_START = dict(num_cpus=8, object_store_memory=128 * 1024 * 1024)


def test_basic_deployment(ray_start):
    @serve.deployment
    class Greeter:
        def __call__(self, name):
            return f"hello {name}"

    handle = serve.run(Greeter.bind(), name="greet", route_prefix=None)
    assert handle.remote("world").result(timeout=30) == "hello world"


def test_function_deployment(ray_start):
    @serve.deployment
    def double(x):
        return x * 2

    handle = serve.run(double.bind(), name="fn", route_prefix=None)
    assert handle.remote(21).result(timeout=30) == 42


def test_multi_replica_distribution(ray_start):
    @serve.deployment(num_replicas=3)
    class Which:
        def __init__(self):
            import os
            self.pid = os.getpid()

        def __call__(self, _):
            return self.pid

    handle = serve.run(Which.bind(), name="which", route_prefix=None)
    pids = {handle.remote(None).result(timeout=30) for _ in range(30)}
    assert len(pids) >= 2   # P2C spreads across replicas


def test_composition(ray_start):
    @serve.deployment
    class Preprocess:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Model:
        def __init__(self, pre):
            self.pre = pre

        def __call__(self, x):
            y = self.pre.remote(x)
            return y.result(timeout=30) * 10

    app = Model.bind(Preprocess.bind())
    handle = serve.run(app, name="composed", route_prefix=None)
    assert handle.remote(4).result(timeout=30) == 50


def test_method_calls(ray_start):
    @serve.deployment
    class Calc:
        def add(self, a, b):
            return a + b

        def mul(self, a, b):
            return a * b

    handle = serve.run(Calc.bind(), name="calc", route_prefix=None)
    assert handle.add.remote(2, 3).result(timeout=30) == 5
    assert handle.mul.remote(2, 3).result(timeout=30) == 6


def test_batching(ray_start):
    @serve.deployment
    class Batched:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
        async def handle_batch(self, items):
            return [(x, len(items)) for x in items]

        async def __call__(self, x):
            return await self.handle_batch(x)

    handle = serve.run(Batched.bind(), name="batched", route_prefix=None)
    resps = [handle.remote(i) for i in range(8)]
    outs = [r.result(timeout=30) for r in resps]
    assert [o[0] for o in outs] == list(range(8))
    assert max(o[1] for o in outs) > 1   # some calls actually batched


def test_status_and_scale_update(ray_start):
    @serve.deployment(num_replicas=1)
    class S:
        def __call__(self, _):
            return 1

    serve.run(S.bind(), name="scaled", route_prefix=None)
    st = serve.status()["scaled"]["S"]
    assert st["running"] == 1
    serve.run(S.options(num_replicas=2).bind(), name="scaled",
              route_prefix=None)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = serve.status()["scaled"]["S"]
        if st["running"] == 2:
            break
        time.sleep(0.3)
    assert st["running"] == 2


def test_http_ingress(ray_start):
    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return {"got": payload}

    serve.run(Echo.bind(), name="http_app", route_prefix="/echo",
              _http=True, http_port=18231)
    import json
    import urllib.request
    req = urllib.request.Request(
        "http://127.0.0.1:18231/echo",
        data=json.dumps({"a": 1}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        body = json.loads(resp.read())
    assert body == {"got": {"a": 1}}


def test_model_multiplexing(ray_start):
    """Many model ids share a replica pool with per-replica LRU caches and
    sticky routing (reference: serve/multiplex.py)."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class MultiModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            self.loads.append(model_id)
            return {"id": model_id, "scale": int(model_id[-1])}

        async def __call__(self, x):
            model_id = serve.get_multiplexed_model_id()
            model = await self.get_model(model_id)
            return x * model["scale"]

        def load_count(self):
            return len(self.loads)

    app = MultiModel.bind()
    serve.run(app, name="mux")
    try:
        h = serve.get_app_handle("mux")
        h2 = h.options(multiplexed_model_id="m2")
        h3 = h.options(multiplexed_model_id="m3")
        assert h2.remote(10).result(timeout=60) == 20
        assert h3.remote(10).result(timeout=60) == 30
        # repeated calls hit the cached model on the same replica: total
        # loads across replicas stays at 2
        for _ in range(6):
            assert h2.remote(1).result(timeout=60) == 2
            assert h3.remote(1).result(timeout=60) == 3
        import ray_tpu
        total_loads = sum(
            ray_tpu.get(r.handle_request.remote("load_count", (), {}),
                        timeout=30)
            for r in h._router.replicas)
        assert total_loads == 2, total_loads
    finally:
        serve.delete("mux")
