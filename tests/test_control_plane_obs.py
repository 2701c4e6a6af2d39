"""Control-plane observability: GCS hot-path handler histograms +
slow-handler spans, launch critical-path attribution, crash black boxes
(write / rotate / seal / stitch), the blackbox CLI merge, and the
metrics-pusher outage-replay fix (reference: Ray's gcs_server exports
per-handler gRPC latency, src/ray/gcs/gcs_server; event_stats.cc's
per-handler queueing stats)."""

import asyncio
import json
import os
import signal
import time

import pytest

from ray_tpu._private import blackbox, events, gcs_obs
from ray_tpu._private.gcs import GcsServer
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util.chaos import GcsRpcDelayer


# ------------------------------------------------- handler instrumentation
def test_handler_histogram_accounting():
    g = GcsServer()
    wrapped = g.obs.wrap_handlers(
        {"kv_put": g.h_kv_put, "kv_get": g.h_kv_get})
    wrapped["kv_put"](None, ns="t", key=b"k", value=b"v")
    for _ in range(9):
        assert wrapped["kv_get"](None, ns="t", key=b"k") == b"v"
    st = g.obs.handlers["kv_get"]
    assert st.calls == 9
    assert sum(st.counts) == 9          # every call lands in one bucket
    assert st.inflight == 0             # fully drained
    assert st.errors == 0
    assert g.obs.inflight_total == 0
    # quantiles are monotone and bounded by the bucket ceiling
    assert 0 < st.p_quantile(0.5) <= st.p_quantile(0.99)
    # registry-shaped rows: histogram counts match, counter matches
    rows = {r["name"]: r for r in g.obs.metric_rows()}
    hist = rows["gcs_rpc_ms"]
    by_handler = {dict(s[0])["handler"]: s for s in hist["samples"]}
    assert sum(by_handler["kv_get"][1]) == 9
    assert len(by_handler["kv_get"][1]) == len(hist["boundaries"]) + 1
    calls = {dict(s[0])["handler"]: s[1]
             for s in rows["gcs_rpc_calls_total"]["samples"]}
    assert calls == {"kv_put": 1.0, "kv_get": 9.0}


def test_handler_error_accounting():
    g = GcsServer()

    def boom(conn, **kw):
        raise ValueError("nope")

    wrapped = g.obs.wrap_handlers({"boom": boom})["boom"]
    for _ in range(3):
        with pytest.raises(ValueError):
            wrapped(None)
    st = g.obs.handlers["boom"]
    assert st.calls == 3 and st.errors == 3 and st.inflight == 0
    rows = {r["name"]: r for r in g.obs.metric_rows()}
    assert rows["gcs_rpc_errors_total"]["samples"][0][1] == 3.0


def test_async_handler_observed():
    g = GcsServer()

    async def slow_echo(conn, x):
        await asyncio.sleep(0)
        return x

    wrapped = g.obs.wrap_handlers({"echo": slow_echo})["echo"]
    out = asyncio.get_event_loop_policy().new_event_loop()
    try:
        assert out.run_until_complete(wrapped(None, x=42)) == 42
    finally:
        out.close()
    st = g.obs.handlers["echo"]
    assert st.calls == 1 and st.inflight == 0


def test_streaming_handlers_not_wrapped():
    g = GcsServer()

    def stream(conn, **kw):
        pass

    stream.streaming = True
    wrapped = g.obs.wrap_handlers({"s": stream})
    assert wrapped["s"] is stream       # different calling convention


def test_slow_handler_emits_span_via_delayer(monkeypatch):
    monkeypatch.setenv("RAY_TPU_GCS_SLOW_RPC_MS", "20")
    g = GcsServer()
    g.h_kv_put(None, ns="t", key=b"k", value=b"v")
    delayer = GcsRpcDelayer("kv_get", 30.0)
    delayer.arm_local()
    try:
        assert gcs_obs.delay_for("kv_get") == 30.0
        wrapped = g.obs.wrap_handlers({"kv_get": g.h_kv_get})["kv_get"]
        loop = asyncio.get_event_loop_policy().new_event_loop()
        try:
            assert loop.run_until_complete(
                wrapped(None, ns="t", key=b"k")) == b"v"
        finally:
            loop.close()
    finally:
        GcsRpcDelayer.disarm_local()
    st = g.obs.handlers["kv_get"]
    assert st.slow == 1
    spans = g.h_list_task_events(None, kind="runtime_event",
                                 category="gcs")
    assert len(spans) == 1
    row = spans[0]
    assert row["name"] == "gcs.rpc"
    assert row["attrs"]["handler"] == "kv_get"
    assert row["attrs"]["ms"] >= 20.0
    # the delayer's env() composes with a prior spec like the other
    # chaos killers
    env = delayer.env(base={gcs_obs.DELAY_ENV: "gcs_rpc=kv_put:5"})
    assert env[gcs_obs.DELAY_ENV] == "gcs_rpc=kv_put:5,gcs_rpc=kv_get:30.0"


def test_sub_threshold_sampling(monkeypatch):
    monkeypatch.setenv("RAY_TPU_GCS_SLOW_RPC_MS", "1000")
    monkeypatch.setenv("RAY_TPU_GCS_RPC_SAMPLE_N", "5")
    g = GcsServer()
    g.h_kv_put(None, ns="t", key=b"k", value=b"v")
    wrapped = g.obs.wrap_handlers({"kv_get": g.h_kv_get})["kv_get"]
    for _ in range(10):
        wrapped(None, ns="t", key=b"k")
    spans = g.h_list_task_events(None, kind="runtime_event",
                                 category="gcs")
    # 1-in-5 sampling over 10 fast calls -> exactly 2 breadcrumbs
    assert len(spans) == 2
    assert g.obs.handlers["kv_get"].slow == 0


# ------------------------------------------------------ launch attribution
def test_launch_span_chain():
    g = GcsServer()
    ent = g._launch_begin("a" * 32, {"name": "MyActor"})
    assert ent is not None and ("a" * 32) in g.launches
    root = ent["root_span_id"]
    t0 = time.time()
    g._launch_span_row(ent, "launch.placement", t0 - 0.01, t0,
                       ent["root_span_id"], node="n1", strategy="DEFAULT")
    loop = asyncio.get_event_loop_policy().new_event_loop()
    try:
        loop.run_until_complete(g.h_launch_phase(
            None, actor_id="a" * 32, phase="worker_obtain"))
    finally:
        loop.close()
    assert g.launches["a" * 32]["phase"] == "worker_obtain"
    g._launch_finish("a" * 32, ok=True)
    assert not g.launches and len(g._launch_done) == 1
    rows = g.h_list_task_events(None, kind="runtime_event",
                                category="launch")
    by_name = {r["name"]: r for r in rows}
    assert by_name["actor.launch"]["span_id"] == root
    assert by_name["actor.launch"]["attrs"]["ok"] is True
    assert by_name["actor.launch"]["attrs"]["total_ms"] >= 0
    child = by_name["launch.placement"]
    assert child["parent_span_id"] == root
    assert child["trace_id"] == by_name["actor.launch"]["trace_id"]
    # stats pane view retires the launch into recent_launch_ms
    stats = g.h_control_plane_stats(None)
    assert stats["launches"] == []
    assert stats["launches_done"] == 1
    assert len(stats["recent_launch_ms"]) == 1


def test_launch_finish_failure_row():
    g = GcsServer()
    g._launch_begin("b" * 32, {"name": "Dead"})
    g._launch_finish("b" * 32, ok=False, error="placement group not ready")
    rows = g.h_list_task_events(None, kind="runtime_event",
                                category="launch")
    root = [r for r in rows if r["name"] == "actor.launch"][0]
    assert root["attrs"]["ok"] is False
    assert "placement" in root["attrs"]["error"]


def test_launch_trace_disabled(monkeypatch):
    monkeypatch.setenv("RAY_TPU_LAUNCH_TRACE_ENABLED", "0")
    g = GcsServer()
    assert g._launch_begin("c" * 32, {"name": "X"}) is None
    assert not g.launches
    g._launch_finish("c" * 32, ok=True)     # no entry -> no row, no crash
    assert g.h_list_task_events(None, kind="runtime_event",
                                category="launch") == []


# ----------------------------------------------------------- black boxes
def test_blackbox_write_and_seal(tmp_path):
    path = str(tmp_path / "worker-1.bbox.ndjson")
    box = blackbox.BlackBox(path, process="worker-1", node_id="n1")
    box.record("marker", event="startup")
    box.on_event({"name": "launch.callable_init", "category": "launch",
                  "kind": "span", "start": 1.0, "end": 2.0,
                  "attrs": {"actor_id": "a1"}})
    box.seal("sigterm")
    box.seal("clean_exit")                  # idempotent: first wins
    recs = blackbox.read_box(path)
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "header"
    assert kinds[-1] == "seal"
    assert recs[-1]["reason"] == "sigterm"
    ev = [r for r in recs if r["kind"] == "event"][0]
    assert ev["name"] == "launch.callable_init"
    assert ev["attrs"] == {"actor_id": "a1"}
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs)             # monotone within a box


def test_blackbox_rotation_bounded(tmp_path):
    path = str(tmp_path / "nm-1.bbox.ndjson")
    box = blackbox.BlackBox(path, max_bytes=8192, process="nm-1")
    for i in range(500):
        box.record("marker", event="tick", i=i, pad="x" * 64)
    live = os.path.getsize(path)
    rotated = os.path.getsize(path + ".1")
    assert live + rotated <= 8192 + 256     # bounded (one line of slack)
    assert rotated > 0                      # rotation actually happened
    recs = blackbox.read_box(path)
    ticks = [r["i"] for r in recs if r.get("event") == "tick"]
    assert ticks[-1] == 499                 # newest history survives
    assert ticks == sorted(ticks)
    # the fresh segment re-headers so a reader of the live file alone
    # still learns the process identity
    with open(path) as f:
        first_live = json.loads(f.readline())
    assert first_live["kind"] == "header" and first_live["rotated"]


def test_blackbox_torn_line_skipped(tmp_path):
    path = str(tmp_path / "gcs-1.bbox.ndjson")
    box = blackbox.BlackBox(path, process="gcs")
    box.record("marker", event="ok")
    with open(path, "a") as f:
        f.write('{"kind": "marker", "event": "torn-by-sig')
    recs = blackbox.read_box(path)
    assert [r for r in recs if r.get("event") == "ok"]
    assert all(r.get("event") != "torn-by-sig" for r in recs)


def test_blackbox_configure_taps_events(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_BLACKBOX_METRICS_INTERVAL_S", "0")
    blackbox.reset()
    events.drain()
    try:
        # a record made BEFORE configure must be backfilled
        events.record_instant("pre.existing", category="test")
        box = blackbox.configure(str(tmp_path), "worker-abc",
                                 node_id="n1", worker_id="w1")
        assert box is not None
        events.record_complete("launch.shell_attach", 1.0, 2.0,
                               category="launch")
        box.seal("clean_exit")
        recs = blackbox.read_box(box.path)
        names = [r.get("name") for r in recs if r["kind"] == "event"]
        assert "pre.existing" in names
        assert "launch.shell_attach" in names
        # the tap mirrors without consuming: the ring still drains
        assert any(r["name"] == "launch.shell_attach"
                   for r in events.peek())
    finally:
        blackbox.reset()
        events.drain()


def test_blackbox_disabled(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_BLACKBOX_ENABLED", "0")
    blackbox.reset()
    try:
        assert blackbox.configure(str(tmp_path), "worker-x") is None
        blackbox.record("marker", event="dropped")   # no-op, no crash
        assert blackbox.count_boxes(str(tmp_path)) == 0
    finally:
        blackbox.reset()


def test_stitch_ordering_and_clock_skew(tmp_path):
    # box A's clock runs 2s AHEAD of the GCS (offset = local - gcs = +2):
    # its raw timestamps must shift BACK to interleave correctly
    a = blackbox.BlackBox(str(tmp_path / "nm-a.bbox.ndjson"),
                          process="nm-a", clock_offset_s=2.0)
    b = blackbox.BlackBox(str(tmp_path / "nm-b.bbox.ndjson"),
                          process="nm-b", clock_offset_s=0.0)
    t = 1000.0
    a.record("marker", event="a1", ts=t + 2.5)   # gcs time t+0.5
    b.record("marker", event="b1", ts=t + 0.1)
    b.record("marker", event="b2", ts=t + 1.0)
    a.seal("clean_exit")
    # b never seals: died hard
    merged = blackbox.stitch(blackbox.scan_boxes(str(tmp_path)))
    order = [m["rec"]["event"] for m in merged["records"]
             if m["rec"].get("kind") == "marker"
             and m["rec"].get("event", "").startswith(("a", "b"))]
    assert order == ["b1", "a1", "b2"]
    by_proc = {x["process"]: x for x in merged["boxes"]}
    assert by_proc["nm-a"]["sealed"]
    assert by_proc["nm-a"]["seal_reason"] == "clean_exit"
    assert not by_proc["nm-b"]["sealed"]
    assert by_proc["nm-b"]["seal_reason"] == "none (died hard)"
    # implausible-skew clamp: a's offset exceeds the tolerance, so its
    # raw timestamps stand and a1 sorts last
    clamped = blackbox.stitch(blackbox.scan_boxes(str(tmp_path)),
                              max_skew_s=1.0)
    order = [m["rec"]["event"] for m in clamped["records"]
             if m["rec"].get("kind") == "marker"
             and m["rec"].get("event", "").startswith(("a", "b"))]
    assert order == ["b1", "b2", "a1"]


def test_blackbox_cli_merge(tmp_path, capsys):
    from ray_tpu.scripts import cli
    box = blackbox.BlackBox(str(tmp_path / "gcs-7.bbox.ndjson"),
                            process="gcs")
    box.record("marker", event="startup")
    box.seal("signal_15")

    class Args:
        paths = [str(tmp_path)]
        json = True
        limit = 0
        max_skew = 0.0

    cli.cmd_blackbox(Args())
    out = json.loads(capsys.readouterr().out)
    assert out["boxes"][0]["seal_reason"] == "signal_15"
    assert [r["rec"]["kind"] for r in out["records"]][-1] == "seal"

    Args.json = False
    cli.cmd_blackbox(Args())
    text = capsys.readouterr().out
    assert "SEALED: signal_15" in text and "gcs" in text


# --------------------------------------- metrics pusher outage buffering
class _FakeWorker:
    def __init__(self, fail: bool):
        self.fail = fail
        self.calls = []

        class Core:
            worker_id = "w-test"
            node_id = "n-test"
        self.core = Core()

    def gcs_call(self, method, **kw):
        if self.fail:
            raise ConnectionError("gcs restarting")
        self.calls.append((method, kw))


@pytest.fixture
def _isolated_registry():
    saved = dict(metrics_mod._registry)
    saved_failed = metrics_mod._failed_push
    metrics_mod._registry.clear()
    metrics_mod._failed_push = None
    yield
    metrics_mod._registry.clear()
    metrics_mod._registry.update(saved)
    metrics_mod._failed_push = saved_failed


def test_push_failure_buffers_and_replays(monkeypatch,
                                          _isolated_registry):
    import ray_tpu
    c = metrics_mod.Counter("cp_test_pushes_total", "test")
    c.inc(5)
    fake = _FakeWorker(fail=True)
    monkeypatch.setattr(ray_tpu, "is_initialized", lambda: True)
    monkeypatch.setattr(ray_tpu, "_get_worker", lambda: fake)
    assert metrics_mod.push_once() is False
    assert metrics_mod._failed_push is not None
    buf_ts, buf_payload = metrics_mod._failed_push
    assert any(r["name"] == "cp_test_pushes_total" for r in buf_payload)

    c.inc(3)
    fake.fail = False
    assert metrics_mod.push_once() is True
    assert metrics_mod._failed_push is None
    assert len(fake.calls) == 2
    # replay first, at its ORIGINAL capture time, then the live push
    replay_kw = fake.calls[0][1]
    assert replay_kw["ts"] == buf_ts
    assert replay_kw["metrics"] is buf_payload
    live_kw = fake.calls[1][1]
    assert "ts" not in live_kw
    # a second consecutive success must not re-send the old snapshot
    metrics_mod.push_once()
    assert len(fake.calls) == 3


def test_replay_reestablishes_delta_baseline(monkeypatch,
                                             _isolated_registry):
    """The reason the buffer exists: a GCS restart wipes the TS delta
    baselines, and without the replay the first post-restart push lands
    the whole cumulative history inside the current window."""
    import ray_tpu
    c = metrics_mod.Counter("cp_test_delta_total", "test")
    c.inc(100)
    fake = _FakeWorker(fail=True)
    monkeypatch.setattr(ray_tpu, "is_initialized", lambda: True)
    monkeypatch.setattr(ray_tpu, "_get_worker", lambda: fake)
    metrics_mod.push_once()                       # buffered
    # age the buffered snapshot past the query window (the outage)
    old_ts, payload = metrics_mod._failed_push
    metrics_mod._failed_push = (old_ts - 120.0, payload)
    c.inc(10)
    fake.fail = False
    assert metrics_mod.push_once() is True

    # replay both pushes into a FRESH GCS (the restart) exactly as the
    # wire saw them
    g = GcsServer()
    for method, kw in fake.calls:
        g.h_report_metrics(None, **kw)
    got = g.h_query_metrics(None, name="cp_test_delta_total",
                            window=60.0, agg="sum")
    # only the post-outage activity lands in the window — not the
    # 100-unit pre-outage history
    assert got["value"] == pytest.approx(10.0)


# ------------------------------------------------------- cluster tier
def test_nm_sigkill_mid_launch_leaves_black_box(tmp_path, monkeypatch):
    """SIGKILL a node manager while an actor launch is in flight on it;
    its black box (continuously appended — nothing runs at death) must
    survive on disk and stitch into the cross-node timeline as a
    died-hard box that still carries its final events."""
    monkeypatch.setenv("RAY_TPU_BLACKBOX_DIR", str(tmp_path))
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 2, "resources": {"head": 1}})
    victim = c.add_node(num_cpus=2, resources={"victim": 1.0})
    try:
        ray_tpu.init(address=c.address)
        c.wait_for_nodes()

        @ray_tpu.remote(resources={"victim": 0.1})
        class Slow:
            def __init__(self):
                time.sleep(30)      # still initializing when killed

            def ping(self):
                return 1

        _ = Slow.remote()           # launch lands on the victim node
        deadline = time.monotonic() + 30
        nm_tag = f"nm-{victim.node_id[:12]}"
        while time.monotonic() < deadline:
            if any(nm_tag in p for p in blackbox.scan_boxes(
                    str(tmp_path))):
                break
            time.sleep(0.2)
        os.kill(victim._local.nm_handle.proc.pid, signal.SIGKILL)
        time.sleep(1.0)
        paths = blackbox.scan_boxes(str(tmp_path))
        nm_boxes = [p for p in paths if nm_tag in p]
        assert nm_boxes, f"no black box for {nm_tag} in {paths}"
        merged = blackbox.stitch(paths)
        nm = [b for b in merged["boxes"] if b["process"] == nm_tag][0]
        assert not nm["sealed"]     # SIGKILL: nothing ran at death
        assert nm["records"] > 0
        nm_recs = [m for m in merged["records"]
                   if m["process"] == nm_tag]
        assert any(m["rec"].get("event") == "startup" for m in nm_recs)
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        c.shutdown()
