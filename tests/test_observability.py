"""OTLP trace export + cluster stack dump (reference:
python/ray/util/tracing/tracing_helper.py:34 OTLP hooks; `ray stack`).
"""

import http.server
import json
import threading
import time

import pytest

import ray_tpu
from ray_tpu.util.tracing import (cluster_stacks, export_otlp,
                                  format_cluster_stacks,
                                  task_events_to_otlp)

pytestmark = pytest.mark.slow


RAY_START = dict(num_cpus=4, object_store_memory=128 * 1024 * 1024)


def test_otlp_mapping_unit():
    rows = [
        {"task_id": "ab" * 12, "name": "f", "trace_id": "11" * 16,
         "span_id": "22" * 8, "parent_span_id": "33" * 8,
         "state_times": {"RUNNING": 10.0, "FINISHED": 10.5},
         "type": "NORMAL_TASK", "node_id": "n", "worker_id": "w",
         "state": "FINISHED"},
        {"task_id": "cd" * 12, "name": "g",
         "state_times": {"RUNNING": 11.0, "FAILED": 11.2},
         "state": "FAILED"},
        {"task_id": "ee" * 12, "name": "never-ran", "state_times": {}},
    ]
    payload = task_events_to_otlp(rows)
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert len(spans) == 2              # never-ran is dropped
    s0 = spans[0]
    assert s0["traceId"] == "11" * 16 and len(s0["traceId"]) == 32
    assert s0["spanId"] == "22" * 8 and len(s0["spanId"]) == 16
    assert s0["parentSpanId"] == "33" * 8
    assert int(s0["endTimeUnixNano"]) - int(s0["startTimeUnixNano"]) == \
        int(0.5e9)
    assert s0["status"]["code"] == 1
    assert spans[1]["status"]["code"] == 2      # FAILED -> error status
    # ids derived from task_id when no trace ctx, still fixed-width hex
    assert len(spans[1]["traceId"]) == 32 and len(spans[1]["spanId"]) == 16


def test_export_otlp_file_and_http(ray_start, tmp_path):
    @ray_tpu.remote
    def traced(x):
        return x + 1

    assert ray_tpu.get([traced.remote(i) for i in range(3)],
                       timeout=60) == [1, 2, 3]

    received = []

    class _Collector(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers["Content-Length"])
            received.append((self.path, json.loads(self.rfile.read(n))))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), _Collector)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        out = str(tmp_path / "traces.json")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            payload = export_otlp(
                filename=out,
                endpoint=f"http://127.0.0.1:{srv.server_port}")
            spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
            if len([s for s in spans if s["name"] == "traced"]) >= 3:
                break
            time.sleep(0.5)     # task events flush asynchronously
        named = [s for s in spans if s["name"] == "traced"]
        assert len(named) >= 3, [s["name"] for s in spans]
        with open(out) as f:
            on_disk = json.load(f)
        assert on_disk["resourceSpans"][0]["resource"]["attributes"][0] \
            == {"key": "service.name", "value": {"stringValue": "ray_tpu"}}
        path, posted = received[-1]
        assert path == "/v1/traces" and "resourceSpans" in posted
    finally:
        srv.shutdown()


def test_cluster_stack_dump(ray_start):
    @ray_tpu.remote
    class Sleeper:
        def nap(self, s):
            import time as _t
            _t.sleep(s)
            return True

    a = Sleeper.remote()
    ref = a.nap.remote(20.0)
    # retry until the nap frame is visible: under load the actor may
    # take several seconds to construct and enter the method
    text = ""
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        dump = cluster_stacks()
        assert dump, "no nodes in stack dump"
        text = format_cluster_stacks(dump)
        if "nap" in text and "_t.sleep(s)" in text:
            break
        time.sleep(1.0)
    # the actor's sleeping frame is visible somewhere in the cluster
    assert "nap" in text and "_t.sleep(s)" in text
    # the node manager's own threads are present
    assert "node_manager" in text
    assert ray_tpu.get(ref, timeout=60) is True
    ray_tpu.kill(a)
