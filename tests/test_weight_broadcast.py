"""Weight-distribution plane: spanning-stripe arena allocation composed
with the tree-relay `ray_tpu.broadcast_weights()` (shm_store.cpp spans +
data_plane.py planning/striping + node_manager relay + worker retry).

Unit tier (any interpreter): binomial fan-out planning, rebroadcast
sharding across surviving holders, adaptive stream counts for
weight-sized transfers, a weight-sized loopback push through a real
DataPlaneServer/Client pair, relay-subtree failure surfacing at the
root's ack, and the runner-set broadcast helper's fallback. The cluster
tier needs the Python 3.12 store runtime like every other multi-node
suite."""

import asyncio
import time

import pytest

from ray_tpu._private import data_plane as dp
from ray_tpu._private.config import cfg

OID = b"\x07" * 20


# --------------------------------------------------------- fan-out planning

def test_binomial_split_covers_every_target_once():
    for n in range(0, 33):
        targets = [f"n{i}" for i in range(n)]
        plan = dp.binomial_split(targets)
        seen = [h for h, _rest in plan]
        for _h, rest in plan:
            seen.extend(rest)
        assert sorted(seen) == sorted(targets)
        assert len(set(seen)) == len(seen)    # nobody pushed twice


def test_binomial_split_source_sends_log_n_copies():
    # the source's direct pushes (plan length) stay O(log n)
    plan = dp.binomial_split([f"n{i}" for i in range(64)])
    assert len(plan) == 7      # ceil(log2(64)) + 1
    assert dp.binomial_split([]) == []
    assert dp.binomial_split(["a"]) == [("a", [])]
    # two targets: both direct (no relay hop for a pair)
    assert dp.binomial_split(["a", "b"]) == [("a", []), ("b", [])]


def test_binomial_split_delegates_half():
    plan = dp.binomial_split([f"n{i}" for i in range(8)])
    # first head carries the other 3 nodes of its half as relay
    assert plan[0] == ("n0", ["n1", "n2", "n3"])


def test_plan_rebroadcast_shards_across_survivors():
    missing = [f"m{i}" for i in range(7)]
    holders = ["h0", "h1", "h2"]
    plan = dp.plan_rebroadcast(missing, holders)
    assigned = [t for _h, tgts in plan for t in tgts]
    assert sorted(assigned) == sorted(missing)
    used = {h for h, _t in plan}
    assert used <= set(holders)
    # round-robin: no holder is more than one target heavier
    sizes = [len(t) for _h, t in plan]
    assert max(sizes) - min(sizes) <= 1


def test_plan_rebroadcast_edge_cases():
    assert dp.plan_rebroadcast([], ["h"]) == []
    assert dp.plan_rebroadcast(["m"], []) == []
    assert dp.plan_rebroadcast(["m"], ["", None]) == []
    assert dp.plan_rebroadcast(["m1", "m2"], ["h"]) == [("h", ["m1", "m2"])]


# ------------------------------------------------------- adaptive streaming

@pytest.fixture()
def _stream_knobs():
    cfg.set("transfer_streams", 2)
    cfg.set("transfer_streams_large", 8)
    cfg.set("transfer_large_object_bytes", 1 << 20)
    yield
    for k in ("transfer_streams", "transfer_streams_large",
              "transfer_large_object_bytes"):
        cfg.reset(k)


def test_adaptive_streams_boundaries(_stream_knobs):
    threshold = 1 << 20
    assert dp.adaptive_streams(0) == 2
    assert dp.adaptive_streams(threshold - 1) == 2
    assert dp.adaptive_streams(threshold) == 8       # at the boundary
    assert dp.adaptive_streams(threshold + 1) == 8
    assert dp.adaptive_streams(100 * threshold) == 8


def test_adaptive_streams_escalation_disabled(_stream_knobs):
    # large <= default disables the escalation entirely
    cfg.set("transfer_streams_large", 2)
    assert dp.adaptive_streams(1 << 30) == 2
    cfg.set("transfer_streams_large", 1)
    assert dp.adaptive_streams(1 << 30) == 2


def test_adaptive_stripe_ranges_compose(_stream_knobs):
    # a weight-sized object fans out across the large stream count, but
    # never below stripe_min bytes per stream
    size = 8 << 20
    ranges = dp.stripe_ranges(size, dp.adaptive_streams(size), 1 << 20)
    assert len(ranges) == 8
    assert sum(length for _o, length in ranges) == size
    small = 512 * 1024
    assert len(dp.stripe_ranges(small, dp.adaptive_streams(small),
                                1 << 20)) == 1


# -------------------------------------------- loopback weight-sized pushes

class FakeNM:
    """Duck-typed stand-in for NodeManager receive bookkeeping (the
    data-plane server only touches `_receiving`, `_finish_receive`,
    `_abort_receive`)."""

    def __init__(self):
        self._receiving = {}
        self.finished = []
        self.aborted = []
        self.relay_result = True

    def begin(self, oid: bytes, size: int) -> bytearray:
        buf = bytearray(size)
        self._receiving[oid] = {"data": memoryview(buf), "remaining": size,
                                "relay": [], "t": time.monotonic()}
        return buf

    def _finish_receive(self, oid: bytes):
        self._receiving.pop(oid)
        self.finished.append(oid)
        return self.relay_result

    def _abort_receive(self, oid: bytes, reason: str):
        self._receiving.pop(oid, None)
        self.aborted.append((oid, reason))


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_weight_sized_push_uses_large_stream_count(_stream_knobs):
    """A payload over the large-object threshold stripes across
    transfer_streams_large raw connections and lands byte-exact."""
    cfg.set("transfer_chunk_bytes", 256 * 1024)
    cfg.set("transfer_stripe_min_bytes", 128 * 1024)
    payload = bytes(range(256)) * (2 * 1024 * 1024 // 256)  # 2 MB >= 1 MB

    async def go():
        nm = FakeNM()
        server = dp.DataPlaneServer(nm)
        addr = await server.start("127.0.0.1")
        client = dp.DataPlaneClient()
        try:
            buf = nm.begin(OID, len(payload))
            stripes = await client.push(addr, OID, memoryview(payload),
                                        len(payload))
            assert len(stripes) == 8      # escalated, not the default 2
            assert sum(stripes) == len(payload)
            assert bytes(buf) == payload
            assert nm.finished == [OID]
        finally:
            client.close()
            await server.close()

    try:
        _run(go())
    finally:
        for k in ("transfer_chunk_bytes", "transfer_stripe_min_bytes"):
            cfg.reset(k)


def test_relay_subtree_failure_surfaces_at_root_ack(_stream_knobs):
    """The completing chunk's ack defers past the receiver's relay
    subtree; a failed subtree turns into FINISH_FAILED and the pusher
    (broadcast root) sees a DataPlaneError — partial delivery is never
    silent."""
    payload = b"w" * (256 * 1024)

    async def go():
        nm = FakeNM()

        async def failing_relay():
            raise RuntimeError("relay node died mid-subtree")

        server = dp.DataPlaneServer(nm)
        addr = await server.start("127.0.0.1")
        client = dp.DataPlaneClient()
        try:
            nm.begin(OID, len(payload))
            nm.relay_result = asyncio.ensure_future(failing_relay())
            with pytest.raises(dp.DataPlaneError):
                await client.push(addr, OID, memoryview(payload),
                                  len(payload))
        finally:
            client.close()
            await server.close()

    _run(go())


# ------------------------------------------------- runner-set weight push

def test_runner_set_broadcast_falls_back_to_put(monkeypatch):
    """Driver loops keep training when the broadcast plane is
    unavailable: the helper degrades to a plain put (runners then pull
    point-to-point as before)."""
    import ray_tpu
    from ray_tpu.rl.actor_manager import FaultTolerantRunnerSet

    rs = FaultTolerantRunnerSet(lambda i: object(), 0)
    calls = {}

    def boom(weights, node_ids=None, **kw):
        calls["broadcast"] = weights
        raise RuntimeError("no cluster")

    def fake_put(v):
        calls["put"] = v
        return "REF"

    monkeypatch.setattr(ray_tpu, "broadcast_weights", boom)
    monkeypatch.setattr(ray_tpu, "put", fake_put)
    out = rs.broadcast_weights({"w": 1})
    assert calls["broadcast"] == {"w": 1}
    assert calls["put"] == {"w": 1}
    assert out == "REF"


def test_runner_set_broadcast_prefers_plane(monkeypatch):
    import ray_tpu
    from ray_tpu.rl.actor_manager import FaultTolerantRunnerSet

    rs = FaultTolerantRunnerSet(lambda i: object(), 0)
    monkeypatch.setattr(ray_tpu, "broadcast_weights",
                        lambda w, node_ids=None, **kw: ("REF", w))
    monkeypatch.setattr(
        ray_tpu, "put",
        lambda v: (_ for _ in ()).throw(AssertionError("put used")))
    assert rs.broadcast_weights({"w": 2}) == ("REF", {"w": 2})


# ------------------------------------------ checkpoint broadcast restore

def test_restore_from_broadcast_places_leaves(monkeypatch):
    np = pytest.importorskip("numpy")
    jax = pytest.importorskip("jax")
    import ray_tpu
    from ray_tpu.train import sharded_checkpoint as sc

    tree = {"w": np.ones((4,), np.float32), "b": np.zeros((2,), np.float32)}
    monkeypatch.setattr(ray_tpu, "get", lambda ref: tree)
    # no abstract tree: the raw host arrays come back as-is
    out = sc.restore_from_broadcast("ref")
    assert out is tree
    # with an abstract tree the leaves are cast/placed per-host
    abstract = {"w": jax.ShapeDtypeStruct((4,), "bfloat16"),
                "b": jax.ShapeDtypeStruct((2,), "float32")}
    placed = sc.restore_from_broadcast("ref", abstract)
    assert placed["w"].dtype == jax.numpy.bfloat16
    assert placed["b"].dtype == jax.numpy.float32


# ----------------------------------------------------------- cluster tier

def test_broadcast_weights_cluster_delivery_and_arrivals():
    """256 KB blob (small for CI; the spanning path has native selftest
    + store-level coverage) reaches every node via the relay tree; each
    receiver records a store.broadcast.arrival instant with bytes."""
    import numpy as np

    import ray_tpu
    import ray_tpu._private.worker as wm
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 1})
    targets = [cluster.add_node(num_cpus=1) for _ in range(3)]
    ray_tpu.init(address=cluster.address)
    try:
        cluster.wait_for_nodes()
        blob = np.arange(256 * 1024, dtype=np.uint8)
        ref = ray_tpu.broadcast_weights(blob)
        view = wm.global_worker.gcs_call("get_cluster_view")
        for t in targets:
            r = wm.global_worker._run(wm.global_worker.core.pool.call(
                view[t.node_id]["address"], "has_object", oid=ref.id))
            assert r["in_store"]
        deadline = time.monotonic() + 30
        arrivals = []
        while time.monotonic() < deadline and len(arrivals) < 3:
            rows = wm.global_worker.gcs_call(
                "list_task_events", kind="runtime_event", limit=20000)
            arrivals = [r for r in rows
                        if r.get("name") == "store.broadcast.arrival"
                        and (r.get("attrs") or {}).get("object_id")
                        == ref.id.hex()[:16]]
            time.sleep(0.5)
        assert len(arrivals) >= 3
        assert all((a.get("attrs") or {}).get("bytes") == blob.nbytes
                   for a in arrivals)
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_broadcast_weights_retries_via_surviving_holders(monkeypatch):
    """Relay-death chaos: every relay-carrying push fails (the interior
    of the tree dies), the root's await surfaces the subtree failure,
    and the retry delivers the missing nodes from the surviving holders
    — exactly-once everywhere, retries observable in the result."""
    import numpy as np

    import ray_tpu
    import ray_tpu._private.worker as wm
    from ray_tpu._private import rpc
    from ray_tpu.util.chaos import BroadcastRelayKiller

    killer = BroadcastRelayKiller(probability=1.0)
    monkeypatch.setenv(killer.SPEC_ENV, killer.spec())
    rpc._CHAOS_SPEC = None      # re-parse the spec in THIS process
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 1})
    targets = [cluster.add_node(num_cpus=1) for _ in range(3)]
    ray_tpu.init(address=cluster.address)
    try:
        cluster.wait_for_nodes()
        blob = np.ones(128 * 1024, dtype=np.uint8)
        ref = ray_tpu.put(blob)
        res = wm.global_worker.broadcast_weights(
            ref, [t.node_id for t in targets], max_retries=3)
        assert res["retries"] >= 1
        view = wm.global_worker.gcs_call("get_cluster_view")
        for t in targets:
            r = wm.global_worker._run(wm.global_worker.core.pool.call(
                view[t.node_id]["address"], "has_object", oid=ref.id))
            assert r["in_store"]
    finally:
        rpc._CHAOS_SPEC = None
        ray_tpu.shutdown()
        cluster.shutdown()
