"""Object-lifetime ledger (ray_tpu/_private/ledger.py + the GCS
object_ledger table): ring discipline, per-node delta/census merge,
leak-detector sweep thresholds, the list_objects join, and the
`ray_tpu memory` CLI helpers. Unit tier runs on any interpreter (no
store import); the cluster tier (synthetic leak flagged within one
sweep, arena-full fragmentation breakdown) is 3.12-gated."""

import asyncio
import time

import pytest

from ray_tpu._private import ledger
from ray_tpu._private.config import cfg
from ray_tpu._private.gcs import GcsServer
from ray_tpu.util.state import _merge_object_rows

OID = bytes(range(20))
OID_HEX = OID.hex()


@pytest.fixture(autouse=True)
def _clean_ledger():
    ledger.set_enabled(True)
    ledger.configure(capacity=4096)
    ledger.drain()
    yield
    ledger.drain()
    ledger.set_enabled(True)
    cfg.reset("ledger_leak_after_s")
    cfg.reset("ledger_max_entries")


# ------------------------------------------------------------- record ring
def test_record_put_shape_and_drain():
    ledger.record_put(OID, size=1234, meta_size=5, owner="w:addr",
                      owner_worker="w1", node_id="n1", task_id="t1",
                      is_span=True)
    batch = ledger.drain()
    assert len(batch) == 1
    rec = batch[0]
    assert rec["object_id"] == OID_HEX
    assert rec["event"] == "created" and rec["sealed"] is True
    assert rec["size"] == 1234 and rec["meta_size"] == 5
    assert rec["is_span"] is True and rec["owner_worker"] == "w1"
    assert rec["seq"] > 0
    assert ledger.drain() == []


def test_disabled_ledger_records_nothing():
    ledger.set_enabled(False)
    ledger.record_put(OID, size=10)
    ledger.record(OID, "freed")
    assert ledger.drain() == []


def test_seq_is_monotonic_per_process():
    ledger.record(OID, "created", size=1)
    ledger.record(OID, "sealed")
    ledger.record(OID, "freed")
    seqs = [r["seq"] for r in ledger.drain()]
    assert seqs == sorted(seqs) and len(set(seqs)) == 3


def test_ring_drops_oldest_and_reports_in_band():
    ledger.configure(capacity=4)
    for i in range(10):
        ledger.record(OID, "refs", refs=i)
    st = ledger.stats()
    assert st["buffered"] == 4 and st["dropped_total"] >= 6
    batch = ledger.drain()
    # drops ride the first record of the next flushed batch
    assert batch[0]["dropped"] >= 6
    assert [r["refs"] for r in batch] == [6, 7, 8, 9]
    # counter reset after a reporting drain
    ledger.record(OID, "refs", refs=42)
    assert "dropped" not in ledger.drain()[0]


# ---------------------------------------------------------- GCS row merge
def _created(seq=1, ts=100.0, **kw):
    rec = {"object_id": OID_HEX, "event": "created", "ts": ts, "seq": seq,
           "size": 1000, "meta_size": 0, "owner": "w:1",
           "owner_worker": "w1", "node_id": "n1", "task_id": "t1",
           "is_span": False, "sealed": True}
    rec.update(kw)
    return rec


def test_gcs_merge_lifecycle():
    g = GcsServer()
    g.h_update_object_ledger(None, records=[_created()], worker_id="w1")
    row = g.object_ledger[OID_HEX]
    assert row["owner"] == "w:1" and row["creator_worker"] == "w1"
    assert row["creator_task"] == "t1"
    assert row["created_ts"] == 100.0 and row["sealed_ts"] == 100.0
    assert list(row["locations"]) == ["n1"]
    # census updates pins + placement
    g.h_update_object_ledger(None, census={"objects": {
        OID_HEX: {"pins": 3, "size": 1000, "is_span": False,
                  "stripe": 2, "age_s": 1.0}}}, node_id="n1")
    assert row["locations"]["n1"]["pins"] == 3
    assert row["stripe"] == 2
    # transfer arrival on a second node, then spill there
    g.h_update_object_ledger(None, records=[
        {"object_id": OID_HEX, "event": "location_add", "ts": 101.0,
         "seq": 1, "node_id": "n2"},
        {"object_id": OID_HEX, "event": "spilled", "ts": 102.0, "seq": 2,
         "node_id": "n2", "size": 1000}])
    assert set(row["locations"]) == {"n1"}
    assert row["spilled_ts"] == 102.0 and row["spilled_on"] == ["n2"]
    g.h_update_object_ledger(None, records=[
        {"object_id": OID_HEX, "event": "restored", "ts": 103.0,
         "seq": 3, "node_id": "n2"}])
    assert set(row["locations"]) == {"n1", "n2"}
    assert row["spilled_on"] == []
    # owner frees: row closes
    g.h_update_object_ledger(None, records=[
        {"object_id": OID_HEX, "event": "freed", "ts": 104.0, "seq": 4,
         "node_id": "n1"}])
    assert row["freed_ts"] == 104.0


def test_census_reconciles_silent_eviction_and_discovery():
    g = GcsServer()
    g.h_update_object_ledger(None, records=[_created()], worker_id="w1")
    other = ("ff" * 20)
    # census: OID vanished (LRU eviction emitted no event), `other`
    # appeared (pre-ledger object discovered by first sighting)
    g.h_update_object_ledger(None, census={"objects": {
        other: {"pins": 1, "size": 77, "is_span": True, "stripe": 0,
                "age_s": 5.0}}}, node_id="n1")
    row = g.object_ledger[OID_HEX]
    assert row["locations"] == {} and row["evicted_ts"] is not None
    drow = g.object_ledger[other]
    assert drow["size"] == 77 and drow["is_span"] is True
    assert drow["locations"]["n1"]["pins"] == 1
    assert drow["sealed_ts"] is not None  # age anchored at sighting


def test_ledger_table_bounded_retires_freed_rows_first():
    g = GcsServer()
    cfg.set("ledger_max_entries", 4)
    try:
        for i in range(4):
            oid = f"{i:02x}" * 20
            g.h_update_object_ledger(None, records=[
                _created(**{"object_id": oid})])
        # free row 2: it should be the eviction victim, not row 0
        g.h_update_object_ledger(None, records=[
            {"object_id": "02" * 20, "event": "freed", "ts": 1.0,
             "seq": 9}])
        g.h_update_object_ledger(None, records=[
            _created(**{"object_id": "aa" * 20})])
        assert "02" * 20 not in g.object_ledger
        assert "00" * 20 in g.object_ledger
        assert len(g.object_ledger) == 4
    finally:
        cfg.reset("ledger_max_entries")


# ------------------------------------------------------------- leak sweep
class _FakeConn:
    closed = False

    def __init__(self):
        self.notifies = []

    async def notify(self, method, **kw):
        self.notifies.append((method, kw))


def _sweep(g, now):
    async def run():
        out = await g.h_ledger_sweep(None, now=now)
        await asyncio.sleep(0)   # let evict-hint notifies run
        return out
    return asyncio.run(run())


def test_sweep_flags_only_past_threshold():
    cfg.set("ledger_leak_after_s", 30.0)
    g = GcsServer()
    g.h_update_object_ledger(None, records=[_created(ts=100.0)])
    g.h_update_object_ledger(None, records=[
        {"object_id": None, "event": "worker_exit", "worker_id": "w1",
         "ts": 100.0, "seq": 2}])
    # too young at t=120
    out = _sweep(g, now=120.0)
    assert out["leaked_objects"] == 0 and not out["newly_flagged"]
    # flagged at t=200 (one sweep)
    out = _sweep(g, now=200.0)
    assert out["leaked_objects"] == 1
    assert out["newly_flagged"] == [OID_HEX]
    assert out["leaked_bytes"] == 1000
    row = g.object_ledger[OID_HEX]
    assert row["leaked"] and row["leak_ts"] == 200.0
    # idempotent: second sweep counts it but doesn't re-flag
    out = _sweep(g, now=210.0)
    assert out["leaked_objects"] == 1 and not out["newly_flagged"]


def test_sweep_exports_gauge_and_leak_instant():
    cfg.set("ledger_leak_after_s", 10.0)
    g = GcsServer()
    g.h_update_object_ledger(None, records=[_created(ts=0.0)])
    g._ledger_exited.add("w1")
    _sweep(g, now=100.0)
    q = g.h_query_metrics(None, "store_leaked_bytes", window=1e9,
                          agg="latest", now=100.0)
    assert q["value"] == 1000.0
    q = g.h_query_metrics(None, "store_leaked_objects", window=1e9,
                          agg="latest", now=100.0)
    assert q["value"] == 1.0
    leaks = [r for r in g.h_list_task_events(None, kind="runtime_event",
                                             category="store")
             if r["name"] == "store.leak"]
    assert len(leaks) == 1
    assert leaks[0]["attrs"]["object_id"] == OID_HEX
    assert leaks[0]["attrs"]["bytes"] == 1000


def test_sweep_sends_eviction_hint_to_holding_node():
    cfg.set("ledger_leak_after_s", 1.0)
    g = GcsServer()
    conn = _FakeConn()
    g.node_conns["n1"] = conn
    g.h_update_object_ledger(None, records=[_created(ts=0.0)])
    g._ledger_exited.add("w1")
    _sweep(g, now=100.0)
    assert conn.notifies == [("ledger_evict_hint",
                              {"oids": [OID_HEX]})]


def test_pins_and_live_owner_protect_from_sweep():
    cfg.set("ledger_leak_after_s", 1.0)
    g = GcsServer()
    # pinned object of a dead owner: protected
    g.h_update_object_ledger(None, records=[_created(ts=0.0)])
    g.h_update_object_ledger(None, census={"objects": {
        OID_HEX: {"pins": 2, "size": 1000, "is_span": False,
                  "stripe": 0, "age_s": 1.0}}}, node_id="n1")
    g._ledger_exited.add("w1")
    assert _sweep(g, now=100.0)["leaked_objects"] == 0
    # unpinned object of a LIVE owner with unknown refs: protected
    other = "bb" * 20
    g.h_update_object_ledger(None, records=[
        _created(ts=0.0, **{"object_id": other,
                            "owner_worker": "alive"})])
    assert _sweep(g, now=100.0)["leaked_objects"] == 0
    # ...until the owner reports zero references
    g.h_update_object_ledger(None, records=[
        {"object_id": other, "event": "refs", "refs": 0, "ts": 1.0,
         "seq": 5}])
    out = _sweep(g, now=100.0)
    assert out["newly_flagged"] == [other]


def test_freed_and_evicted_rows_never_flag():
    cfg.set("ledger_leak_after_s", 1.0)
    g = GcsServer()
    g.h_update_object_ledger(None, records=[_created(ts=0.0)])
    g._ledger_exited.add("w1")
    _sweep(g, now=50.0)
    assert g.object_ledger[OID_HEX]["leaked"]
    # the holding node reclaims it (hint consumed): census drops it
    g.h_update_object_ledger(None, census={"objects": {}}, node_id="n1")
    out = _sweep(g, now=60.0)
    assert out["leaked_objects"] == 0
    assert g.object_ledger[OID_HEX]["leaked"] is False


# -------------------------------------------------------- list_objects join
def _shm_row(hexid, **kw):
    row = {"object_id": hexid, "node_id": "n1", "size_bytes": 100,
           "kind": "shm", "pins": 1, "is_span": False, "stripe": 0,
           "age_s": 5, "sealed": True}
    row.update(kw)
    return row


def test_merge_rows_join_and_order_is_deterministic():
    shm = [_shm_row("aa" * 20)]
    owned = {bytes.fromhex("aa" * 20): {"complete": True,
                                        "location": "n1",
                                        "borrowers": set(),
                                        "submitted": 0}}
    led = [{"object_id": "aa" * 20, "owner": "w:1", "creator_task": "t1",
            "created_ts": 1.0, "sealed_ts": 1.0, "size": 100,
            "locations": {"n1": {"pins": 9}}, "leaked": False},
           {"object_id": "bb" * 20, "owner": "w:2", "created_ts": 2.0,
            "sealed_ts": 2.0, "size": 999, "meta_size": 1,
            "is_span": True, "locations": {"n2": {"pins": 0}},
            "leaked": True}]
    a = _merge_object_rows(shm, owned, led, 10, node_id="n1", now=50.0)
    b = _merge_object_rows(shm, owned, led, 10, node_id="n1", now=50.0)
    assert a == b
    # shm+owned row keeps live truth (pins=1 from the arena, NOT the
    # ledger's 9) and gains provenance
    r0 = a[0]
    assert r0["kind"] == "owned+shm" and r0["pins"] == 1
    assert r0["owner"] == "w:1" and r0["creator_task"] == "t1"
    assert r0["age_s"] == 5       # live age wins
    # ledger-only row: provenance-derived columns
    r1 = a[1]
    assert r1["kind"] == "ledger" and r1["is_span"] is True
    assert r1["size_bytes"] == 1000 and r1["leaked"] is True
    assert r1["age_s"] == 48.0 and r1["node_id"] == "n2"


def test_merge_rows_every_row_has_new_columns():
    shm = [_shm_row("aa" * 20)]
    owned = {bytes.fromhex("cc" * 20): {"complete": False,
                                        "location": None,
                                        "borrowers": set(),
                                        "submitted": 1}}
    out = _merge_object_rows(shm, owned, [], 10, node_id="n1", now=1.0)
    for row in out:
        assert "is_span" in row and "pins" in row and "age_s" in row


def test_merge_rows_respects_limit_shm_first():
    shm = [_shm_row(f"{i:02x}" * 20) for i in range(5)]
    led = [{"object_id": "ee" * 20, "size": 1, "locations": {},
            "created_ts": 1.0, "sealed_ts": 1.0}]
    out = _merge_object_rows(shm, {}, led, 3, now=2.0)
    assert len(out) == 3
    assert all(r["kind"] == "shm" for r in out)


# ------------------------------------------------------------- CLI helpers
def test_cli_memory_sort_group_format():
    from ray_tpu.scripts.cli import (_format_memory_rows, _memory_grouped,
                                     _memory_sorted)
    rows = [
        {"object_id": "a" * 40, "kind": "owned+shm", "size_bytes": 10,
         "pins": 0, "age_s": 100.0, "is_span": False, "owner": "w:1",
         "node_id": "n1", "locations": ["n1"]},
        {"object_id": "b" * 40, "kind": "ledger", "size_bytes": 999,
         "pins": 2, "age_s": 1.0, "is_span": True, "owner": "w:2",
         "node_id": "n2", "locations": ["n1", "n2"], "leaked": True},
    ]
    assert [r["size_bytes"] for r in _memory_sorted(rows, "size")] \
        == [999, 10]
    assert [r["age_s"] for r in _memory_sorted(rows, "age")] \
        == [100.0, 1.0]
    assert [r["node_id"] for r in _memory_sorted(rows, "node")] \
        == ["n1", "n2"]
    groups = {g["group"]: g for g in _memory_grouped(rows, "owner")}
    assert groups["w:2"]["leaked_bytes"] == 999
    assert groups["w:1"]["bytes"] == 10
    text = _format_memory_rows(rows)
    assert "LEAK" in text and "yes" in text and "w:1" in text


def test_cli_memory_pane_renders_available_metrics():
    from ray_tpu.scripts import cli as cli_mod

    class FakeState:
        @staticmethod
        def query_metrics(name, window, agg):
            if name == "store_bytes_in_use":
                return {"value": 12345.0}
            if name == "data_plane_bytes_in_total":
                return {"value": 1e6}
            return {"value": None}
    pane = cli_mod._memory_pane(FakeState, 30.0)
    assert "arena bytes in use" in pane
    assert "data-plane B/s in" in pane
    assert "leaked" not in pane   # no value pushed -> row omitted


# ------------------------------------------------------------ cluster tier
def test_arena_full_error_carries_fragmentation_breakdown(tmp_path):
    from ray_tpu._private import events
    from ray_tpu._private.object_store import ObjectStoreClient
    store = ObjectStoreClient(str(tmp_path / "frag_store"), create=True,
                              size=4 * 1024 * 1024, stripes=1)
    try:
        # unevictable objects so the create cannot make room
        for i in range(3):
            bufs = store.create(bytes([i]) * 20, 1024 * 1024,
                                evictable=False)
            assert bufs is not None
            store.seal(bytes([i]) * 20)
        events.drain()
        with pytest.raises(MemoryError) as ei:
            store.create(b"Z" * 20, 64 * 1024 * 1024)
        msg = str(ei.value)
        assert "requested=" in msg and "live=" in msg \
            and "hole=" in msg
        recs = [r for r in events.drain()
                if r["name"] == "store.arena_full"]
        assert recs and "stripes" in recs[0]["attrs"]
        # live arena truth probes
        info = store.object_info(bytes([0]) * 20)
        assert info["sealed"] and info["data_size"] == 1024 * 1024
        frag = store.fragmentation()
        assert frag["stripes"][0]["live"] >= 3 * 1024 * 1024
    finally:
        store.close()


def test_node_manager_consumes_evict_hints():
    from ray_tpu._private.node_manager import NodeManager
    from ray_tpu._private.object_store import ObjectStoreClient
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        store = ObjectStoreClient(d + "/hint_store", create=True,
                                  size=4 * 1024 * 1024, stripes=1)
        try:
            oid = b"L" * 20
            store.put_bytes(oid, b"x" * 4096)

            class Stub:
                pass
            stub = Stub()
            stub.store = store
            stub._evict_hints = set()
            stub.node_id = "n1"     # the eviction is recorded under it
            NodeManager.h_ledger_evict_hint(stub, None, [oid.hex()])
            assert oid in stub._evict_hints
            freed = NodeManager._consume_evict_hints(stub, {0}, False)
            assert freed >= 4096
            assert not store.contains(oid)
            assert oid not in stub._evict_hints
        finally:
            store.close()


def test_cluster_synthetic_leak_flagged_within_one_sweep():
    """Acceptance: a sealed object whose owner (an actor worker) was
    killed with no pins outstanding is flagged by ONE explicit ledger
    sweep, and its bytes land in store_leaked_bytes."""
    import os

    import numpy as np
    os.environ["RAY_TPU_LEDGER_LEAK_AFTER_S"] = "1"
    import ray_tpu
    from ray_tpu.util import state
    cfg.set("ledger_leak_after_s", 1.0)
    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024 * 1024)
    try:
        @ray_tpu.remote
        class Leaker:
            def leak(self):
                # owner keeps the ref alive so it is never freed; the
                # ref dies WITH the worker -> classic leak shape
                self.ref = ray_tpu.put(np.ones(300_000, dtype=np.uint8))
                return self.ref.id.hex()

        a = Leaker.remote()
        leaked_hex = ray_tpu.get(a.leak.remote())
        time.sleep(2.5)    # ledger flush (1s cadence) + census tick
        ray_tpu.kill(a)
        deadline = time.time() + 30
        flagged = None
        while time.time() < deadline:
            time.sleep(1.0)
            out = state.ledger_sweep()
            if leaked_hex in (out.get("newly_flagged") or ()) \
                    or any(r["object_id"] == leaked_hex
                           for r in state.list_object_ledger(leaked=True)):
                flagged = out
                break
        assert flagged is not None, "synthetic leak never flagged"
        q = state.query_metrics("store_leaked_bytes", window=120,
                                agg="latest")
        assert (q["value"] or 0) >= 300_000
        rows = [r for r in state.list_objects(limit=2000)
                if r.get("object_id") == leaked_hex]
        assert rows and rows[0]["leaked"]
        assert rows[0].get("size_bytes", 0) >= 300_000
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_LEDGER_LEAK_AFTER_S", None)
        cfg.reset("ledger_leak_after_s")
