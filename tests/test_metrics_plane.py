"""Time-series metrics plane (ray_tpu/_private/metrics_ts.py) + SLO
burn-rate engine (ray_tpu/serve/slo.py): ring retention/eviction
determinism, counter-delta and histogram-delta storage, percentile
reconstruction vs exact values, query window edges, GCS handler wiring,
burn-rate transitions under synthetic pushes, pusher hardening, and the
chrome-trace counter tracks. All CPU-only, no cluster."""

import random
import sys
import threading
import time

import numpy as np
import pytest

from ray_tpu._private import events
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.metrics_ts import (MetricsTimeSeries,
                                         fraction_over,
                                         percentile_from_buckets)
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util.metrics import (Histogram, counter_snapshot,
                                  gauge_snapshot, render_prometheus)


def _counter_row(name, value, tags=None):
    return counter_snapshot(name, value, tags=tags)


def _gauge_row(name, value, tags=None):
    return gauge_snapshot(name, value, tags=tags)


# ------------------------------------------------------------- ring storage
def test_counter_deltas_and_reset_detection():
    ts = MetricsTimeSeries()
    for t, v in [(0, 5.0), (2, 8.0), (4, 8.0), (6, 3.0), (8, 10.0)]:
        ts.ingest("w1", [_counter_row("c", v)], ts=100.0 + t)
    # deltas: 5 (first), 3, skip (unchanged), 3 (reset -> full value), 7
    q = ts.query("c", window_s=60, agg="sum", now=110.0)
    assert q["value"] == 18.0
    assert q["n_samples"] == 4          # the unchanged push stored nothing
    # (100, 110] excludes the first delta (left-exclusive edge): 3+3+7
    assert ts.query("c", window_s=10, agg="rate", now=110.0)["value"] \
        == pytest.approx(1.3)


def test_ring_eviction_is_deterministic_oldest_first():
    ts = MetricsTimeSeries(max_samples=4)
    for i in range(10):
        ts.ingest("w1", [_gauge_row("g", float(i))], ts=100.0 + i)
    q = ts.query("g", window_s=100, agg="series", now=200.0)
    kept = [v for _, v in q["series"][0]["samples"]]
    assert kept == [6.0, 7.0, 8.0, 9.0]     # exactly the newest 4


def test_retention_trims_old_samples():
    ts = MetricsTimeSeries(retention_s=10.0)
    ts.ingest("w1", [_gauge_row("g", 1.0)], ts=100.0)
    ts.ingest("w1", [_gauge_row("g", 2.0)], ts=120.0)   # 100.0 aged out
    s = ts.series["g"][((), "w1")]
    assert [v for _, v in s.samples] == [2.0]


def test_series_cap_drops_new_series():
    ts = MetricsTimeSeries(max_series=2)
    ts.ingest("w1", [_gauge_row("g1", 1.0), _gauge_row("g2", 1.0),
                     _gauge_row("g3", 1.0)], ts=100.0)
    assert ts.stats()["n_series"] == 2
    assert ts.stats()["dropped_series"] == 1


def test_window_edges_left_exclusive_right_inclusive():
    ts = MetricsTimeSeries()
    for t in (100.0, 102.0, 104.0):
        ts.ingest("w1", [_gauge_row("g", t)], ts=t)
    # (100, 104]: the sample AT the left edge is excluded, the right
    # edge included — two adjacent windows partition samples exactly
    q = ts.query("g", window_s=4.0, agg="series", now=104.0)
    assert [t for t, _ in q["series"][0]["samples"]] == [102.0, 104.0]
    q_prev = ts.query("g", window_s=4.0, agg="series", now=100.0)
    assert [t for t, _ in q_prev["series"][0]["samples"]] == [100.0]


def test_gauge_aggregates_across_workers():
    ts = MetricsTimeSeries()
    ts.ingest("w1", [_gauge_row("g", 2.0)], ts=100.0)
    ts.ingest("w2", [_gauge_row("g", 6.0)], ts=101.0)
    assert ts.query("g", 60, "avg", now=102.0)["value"] == 4.0
    assert ts.query("g", 60, "max", now=102.0)["value"] == 6.0
    assert ts.query("g", 60, "min", now=102.0)["value"] == 2.0
    assert ts.query("g", 60, "latest", now=102.0)["value"] == 6.0


def test_tags_filter_subset_match():
    ts = MetricsTimeSeries()
    ts.ingest("w1", [_counter_row("c", 5.0, {"zone": "a"}),
                     _counter_row("c", 7.0, {"zone": "b"})], ts=100.0)
    assert ts.query("c", 60, "sum", now=101.0)["value"] == 12.0
    assert ts.query("c", 60, "sum", tags={"zone": "a"},
                    now=101.0)["value"] == 5.0
    assert ts.query("c", 60, "sum", tags={"zone": "nope"},
                    now=101.0)["value"] is None


# -------------------------------------------------- histogram reconstruction
def test_percentile_reconstruction_against_exact():
    random.seed(7)
    ts = MetricsTimeSeries(max_samples=2000)
    h = Histogram("ttft", boundaries=[1, 2, 5, 10, 20, 50, 100, 200,
                                      500, 1000])
    vals = []
    now = 100.0
    for _ in range(40):
        for _ in range(25):
            v = random.lognormvariate(3.0, 1.0)
            vals.append(v)
            h.observe(v)
        ts.ingest("w1", [h._snapshot()], ts=now)
        now += 2.0
    arr = np.array(vals)
    bounds = h.boundaries
    for agg, q in [("p50", 50), ("p95", 95), ("p99", 99)]:
        got = ts.query("ttft", window_s=1000, agg=agg, now=now)["value"]
        exact = float(np.percentile(arr, q))
        # reconstruction is exact up to the containing bucket's width
        bucket_hi = next((b for b in bounds if b >= exact), bounds[-1])
        bucket_lo = max([0.0] + [b for b in bounds if b < exact])
        assert bucket_lo <= got <= max(bucket_hi, exact) + 1e-9, \
            (agg, got, exact)
    # mean reconstructs exactly (sum deltas / count deltas)
    assert ts.query("ttft", 1000, "avg", now=now)["value"] == \
        pytest.approx(arr.mean(), rel=1e-6)
    # frac_over within one bucket of exact
    frac = ts.query("ttft", 1000, "frac_over", threshold=50.0,
                    now=now)["value"]
    assert abs(frac - float((arr > 50).mean())) < 0.08


def test_histogram_window_isolates_old_observations():
    """Observations before the window must not leak into the windowed
    percentile: push slow requests first, fast ones later."""
    ts = MetricsTimeSeries()
    h = Histogram("lat", boundaries=[10, 100, 1000])
    now = 100.0
    for _ in range(10):
        for _ in range(20):
            h.observe(900.0)
        ts.ingest("w1", [h._snapshot()], ts=now)
        now += 2.0
    for _ in range(10):
        for _ in range(20):
            h.observe(5.0)
        ts.ingest("w1", [h._snapshot()], ts=now)
        now += 2.0
    recent = ts.query("lat", window_s=20.0, agg="p95", now=now)
    overall = ts.query("lat", window_s=1000.0, agg="p95", now=now)
    assert recent["value"] <= 10.0
    assert overall["value"] > 100.0


def test_percentile_and_fraction_helpers_edge_cases():
    assert percentile_from_buckets([10.0], [0, 0], 0.95) is None
    # all mass in the overflow bucket clamps to the top boundary
    assert percentile_from_buckets([10.0, 20.0], [0, 0, 5], 0.5) == 20.0
    # interpolation: uniform mass in (0, 10], p50 -> 5
    assert percentile_from_buckets([10.0], [10, 0], 0.5) == \
        pytest.approx(5.0)
    assert fraction_over([10.0], [10, 0], 5.0) == pytest.approx(0.5)
    assert fraction_over([10.0], [0, 10], 10.0) == 1.0


# ------------------------------------------------------------ GCS handlers
def test_gcs_report_and_query_roundtrip():
    g = GcsServer()
    h = Histogram("serve_llm_ttft_ms",
                  boundaries=[10, 50, 100, 250, 500])
    now = 1000.0
    for _ in range(20):
        for _ in range(10):
            h.observe(40.0)
        g.h_report_metrics(None, "w1", [h._snapshot()], ts=now)
        now += 2.0
    q = g.h_query_metrics(None, "serve_llm_ttft_ms", window=30,
                          agg="p95", now=now)
    assert q["value"] is not None and 10.0 <= q["value"] <= 50.0
    names = {r["name"] for r in g.h_list_metric_series(None)}
    assert "serve_llm_ttft_ms" in names
    # latest-snapshot table (the /metrics render path) still works
    assert "w1" in g.h_get_metrics(None)
    # dropping the worker clears delta baselines but keeps history
    g.h_drop_worker_metrics(None, "w1")
    q2 = g.h_query_metrics(None, "serve_llm_ttft_ms", window=30,
                           agg="p95", now=now)
    assert q2["value"] == q["value"]


def test_gcs_dump_series_gauges_for_counter_tracks():
    g = GcsServer()
    for i in range(5):
        g.h_report_metrics(None, "w1",
                           [_gauge_row("occupancy", float(i))],
                           ts=100.0 + i)
    rows = g.h_dump_metric_series(None, kinds=["gauge"], now=105.0)
    assert len(rows) == 1 and rows[0]["name"] == "occupancy"
    assert len(rows[0]["samples"]) == 5


def test_chrome_counter_tracks_from_gauge_series():
    from ray_tpu.util.tracing import task_events_to_chrome
    series = [{"name": "queue_depth", "kind": "gauge",
               "tags": {"node": "n0"}, "worker_id": "w1",
               "samples": [[10.0, 1.0], [12.0, 4.0]]}]
    out = task_events_to_chrome([], gauge_series=series)
    assert len(out) == 2
    assert all(e["ph"] == "C" and e["pid"] == "metrics" for e in out)
    assert out[0]["name"] == "queue_depth{node=n0}"
    assert out[0]["args"]["value"] == 1.0
    assert [e["ts"] for e in out] == [10.0 * 1e6, 12.0 * 1e6]
    # counter events and span events sort into one timeline
    span_rows = [{"task_id": "t", "name": "f", "state": "FINISHED",
                  "state_times": {"RUNNING": 11.0, "FINISHED": 11.5}}]
    merged = task_events_to_chrome(span_rows, gauge_series=series)
    assert [e["ts"] for e in merged] == sorted(e["ts"] for e in merged)


# ------------------------------------------------------------- SLO engine
def _fill_ttft(g, h, value, pushes, now, per_push=20):
    for _ in range(pushes):
        for _ in range(per_push):
            h.observe(value)
        g.h_report_metrics(None, "w1", [h._snapshot()], ts=now)
        now += 2.0
    return now


def test_slo_burn_rate_transitions_under_synthetic_pushes():
    from ray_tpu.serve.slo import SloConfig, SloTracker
    events.drain()
    g = GcsServer()
    h = Histogram("serve_llm_ttft_ms",
                  boundaries=[10, 50, 100, 250, 500, 1000, 2500])
    now = 1000.0
    tracker = SloTracker()
    slo = SloConfig(p95_ttft_ms=200.0, fast_window_s=30.0,
                    slow_window_s=120.0)
    clock = {"now": now}

    def query(metric, window=60.0, agg="avg", tags=None, threshold=None):
        return g.h_query_metrics(None, metric, window=window, agg=agg,
                                 tags=tags, threshold=threshold,
                                 now=clock["now"])

    # healthy: 40ms TTFT
    clock["now"] = _fill_ttft(g, h, 40.0, 30, clock["now"])
    rows = tracker.update("app", "llm", slo, query)
    assert rows[0]["objective"] == "latency"
    assert not rows[0]["violating"] and rows[0]["burn_fast"] == 0.0

    # induced load: 800ms TTFT; fast window burns first, then slow
    clock["now"] = _fill_ttft(g, h, 800.0, 5, clock["now"])
    fast_only = tracker.update("app", "llm", slo, query)[0]
    assert fast_only["burn_fast"] > 1.0
    clock["now"] = _fill_ttft(g, h, 800.0, 55, clock["now"])
    rows = tracker.update("app", "llm", slo, query)
    assert rows[0]["violating"]
    drained = [r["name"] for r in events.drain()
               if r.get("state") == "RUNNING"]
    assert "slo.violation" in drained
    # the violation is also a gauge on the metrics plane
    snap = {m["name"]: m for m in metrics_mod.registry_snapshot()}
    viol = dict((tuple(sorted(dict(k).items())), v)
                for k, v in snap["slo_violating"]["samples"])
    key = tuple(sorted({"app": "app", "deployment": "llm",
                        "objective": "latency"}.items()))
    assert viol[key] == 1.0

    # recovery: fast traffic again long enough to drain both windows
    clock["now"] = _fill_ttft(g, h, 30.0, 80, clock["now"])
    rows = tracker.update("app", "llm", slo, query)
    assert not rows[0]["violating"]
    drained = [r["name"] for r in events.drain()
               if r.get("state") == "RUNNING"]
    assert "slo.recovered" in drained
    # no repeated violation events while state is unchanged
    tracker.update("app", "llm", slo, query)
    assert "slo.violation" not in [r["name"] for r in events.drain()]


def test_slo_error_rate_objective():
    from ray_tpu.serve.slo import evaluate_slo
    g = GcsServer()
    now = 1000.0
    total = err = 0.0
    for i in range(40):
        total += 10.0
        if i >= 20:
            err += 5.0      # 50% errors in the recent half
        g.h_report_metrics(None, "w1", [
            _counter_row("serve_llm_requests_total", total),
            _counter_row("serve_llm_requests_total", err,
                         {"finish_reason": "error"}),
        ], ts=now)
        now += 2.0

    def query(metric, window=60.0, agg="avg", tags=None, threshold=None):
        return g.h_query_metrics(None, metric, window=window, agg=agg,
                                 tags=tags, threshold=threshold, now=now)

    rows = evaluate_slo({"max_error_rate": 0.05,
                         "fast_window_s": 30.0, "slow_window_s": 60.0},
                        query)
    assert rows[0]["objective"] == "error_rate"
    assert rows[0]["violating"]
    assert rows[0]["burn_fast"] > 1.0


def test_slo_no_traffic_means_no_burn():
    from ray_tpu.serve.slo import evaluate_slo

    def query(metric, window=60.0, agg="avg", tags=None, threshold=None):
        return {"value": None, "n_samples": 0}

    rows = evaluate_slo({"p95_ttft_ms": 100.0, "max_error_rate": 0.01},
                        query)
    assert len(rows) == 2
    assert all(not r["violating"] and r["burn_fast"] == 0.0 for r in rows)


# ------------------------------------------------------- pusher hardening
def test_push_interval_is_jittered_within_bounds():
    vals = {metrics_mod._push_interval() for _ in range(50)}
    assert all(1.5 <= v <= 2.5 for v in vals)
    assert len(vals) > 1        # actually jittered, not constant


def test_pusher_stop_and_resume_lifecycle():
    # force-start a pusher, stop it, confirm the thread exits, resume
    metrics_mod._ensure_pusher()
    assert metrics_mod._pusher_started
    t = next((th for th in threading.enumerate()
              if th.name == "metrics-push"), None)
    assert t is not None
    metrics_mod.stop_pusher()
    t.join(timeout=10)
    assert not t.is_alive()
    assert not metrics_mod._pusher_started
    # resume restarts only when the registry is non-empty; the suite
    # has registered metrics by now, so it restarts
    metrics_mod.resume_pusher()
    assert metrics_mod._pusher_started == bool(metrics_mod._registry)


def test_push_once_logs_first_failure_only(caplog, monkeypatch):
    import logging

    monkeypatch.setattr(metrics_mod, "_push_failures", 0)

    class _FakeRay:
        @staticmethod
        def is_initialized():
            return True

        @staticmethod
        def _get_worker():
            raise ConnectionError("gcs down")

    import sys
    monkeypatch.setitem(sys.modules, "ray_tpu", _FakeRay)
    # a metric must exist or push_once returns before contacting the GCS
    metrics_mod.Gauge("pusher_probe_gauge", "t").set(1.0)
    with caplog.at_level(logging.WARNING,
                         logger="ray_tpu.util.metrics"):
        assert metrics_mod.push_once() is False
        assert metrics_mod.push_once() is False
    warn = [r for r in caplog.records
            if "metrics push to GCS failed" in r.message]
    assert len(warn) == 1


# ------------------------------------------- daemon snapshots / prometheus
def test_daemon_snapshots_render_and_ingest():
    rows = [counter_snapshot("data_plane_bytes_in_total", 12345,
                             "bytes", {"node": "n0"}),
            gauge_snapshot("data_plane_active_conns", 3,
                           "conns", {"node": "n0"})]
    text = render_prometheus({"nm:n0": rows})
    assert 'data_plane_bytes_in_total{node="n0"} 12345.0' in text
    assert 'data_plane_active_conns{node="n0"} 3.0' in text
    ts = MetricsTimeSeries()
    ts.ingest("nm:n0", rows, ts=100.0)
    ts.ingest("nm:n0", [counter_snapshot(
        "data_plane_bytes_in_total", 22345, tags={"node": "n0"})],
        ts=102.0)
    assert ts.query("data_plane_bytes_in_total", 60, "sum",
                    now=103.0)["value"] == 22345.0
    assert ts.query("data_plane_bytes_in_total", 2, "rate",
                    now=102.0)["value"] == pytest.approx(5000.0)


# ----------------------------------------------------------- cluster tier
@pytest.fixture(scope="module")
def cluster():
    from ray_tpu.cluster_utils import Cluster
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    yield c
    c.shutdown()


def test_live_windowed_query_reconstructs_percentile(cluster):
    """Acceptance: query_metrics("serve_ttft_ms", window=30, agg="p95")
    returns a correct percentile reconstructed from histogram deltas
    pushed by a live worker process."""
    import ray_tpu
    from ray_tpu.util import state
    from ray_tpu.util.metrics import Histogram, push_once
    ray_tpu.init(address=cluster.address)
    try:
        h = Histogram("serve_ttft_ms",
                      boundaries=[10, 50, 100, 250, 500, 1000])
        # 94.5% of requests at ~40ms, 5.5% at ~400ms -> p95 in (250, 500]
        # (at exactly 95% / 5% the 95th percentile IS the 50 ms boundary)
        for i in range(400):
            h.observe(400.0 if i % 19 == 0 else 40.0)
        assert push_once()
        deadline = time.monotonic() + 30
        q = {}
        while time.monotonic() < deadline:
            q = state.query_metrics("serve_ttft_ms", window=30,
                                    agg="p95")
            if q.get("value") is not None:
                break
            time.sleep(0.5)
        assert q.get("value") is not None, q
        assert 100.0 < q["value"] <= 500.0, q
        exact = state.query_metrics("serve_ttft_ms", window=30,
                                    agg="avg")
        assert exact["value"] == pytest.approx(59.8, rel=0.05)
        # the new data-plane registry metrics surface too (node manager
        # pushes its own snapshots on the 2s cadence)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            names = {r["name"] for r in state.list_metric_series()}
            if "data_plane_bytes_in_total" in names:
                break
            time.sleep(0.5)
        assert "data_plane_bytes_in_total" in names
    finally:
        ray_tpu.shutdown()


def test_induced_load_produces_slo_violation_event(cluster):
    """Acceptance: a Serve deployment with an SLO, driven past its TTFT
    target, yields an slo.violation runtime event visible via
    list_runtime_events."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util import state
    from ray_tpu.util.metrics import Histogram, push_once
    ray_tpu.init(address=cluster.address)
    try:
        @serve.deployment(slo_config={"p95_ttft_ms": 100.0,
                                      "latency_metric": "probe_ttft_ms",
                                      "fast_window_s": 10.0,
                                      "slow_window_s": 20.0})
        def noop(x):
            return x

        serve.run(noop.bind(), name="slo-probe", route_prefix=None)
        # induce load: every request blows the 100ms target
        h = Histogram("probe_ttft_ms",
                      boundaries=[10, 50, 100, 250, 500, 1000])
        deadline = time.monotonic() + 90
        seen = False
        while time.monotonic() < deadline and not seen:
            for _ in range(50):
                h.observe(400.0)
            push_once()
            rows = state.list_runtime_events(category="serve")
            seen = any(r.get("name") == "slo.violation" for r in rows)
            time.sleep(1.0)
        assert seen, "no slo.violation event reached the GCS"
        slo = serve.slo_status()
        row = slo["slo-probe"]["noop"][0]
        assert row["violating"] and row["burn_fast"] > 1.0
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


def test_node_manager_observability_payload_shape():
    """The node manager's payload builder produces registry-shaped rows
    without a running node manager (the data-plane counters satellite)."""
    nm_mod = pytest.importorskip(
        "ray_tpu._private.node_manager",
        reason="node manager import needs the >=3.12 object store")
    NodeManager = nm_mod.NodeManager

    class _DS:
        bytes_in, chunks_in, active_conns = 100, 2, 1

    class _DC:
        bytes_out, chunks_out = 50, 1

    nm = NodeManager.__new__(NodeManager)      # no __init__: unit shape
    nm.node_id = "deadbeef" * 4
    nm.workers = {}
    nm.store = None
    nm._data_server = _DS()
    nm._data_client = _DC()
    nm._receiving = {}
    nm._launch_phase_ms, nm._launches_total = {}, 0
    rows = nm._observability_metrics()
    by_name = {r["name"]: r for r in rows}
    assert by_name["data_plane_bytes_in_total"]["type"] == "counter"
    assert by_name["data_plane_bytes_in_total"]["samples"][0][1] == 100.0
    assert by_name["data_plane_active_conns"]["type"] == "gauge"
    assert by_name["data_plane_receiving"]["samples"][0][1] == 0.0
    # tags carry the node id so per-node series stay distinguishable
    assert dict(by_name["data_plane_bytes_out_total"]["samples"][0][0])[
        "node"] == nm.node_id[:12]
