"""State API + job submission + dashboard REST tests (reference:
python/ray/tests/test_state_api.py, dashboard/modules/job/tests)."""

import json
import sys
import time
import urllib.request

import ray_tpu
from ray_tpu.util import state


RAY_START = dict(num_cpus=4, object_store_memory=128 * 1024 * 1024)


def test_list_nodes(ray_start):
    nodes = state.list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]


def test_task_events(ray_start):
    @ray_tpu.remote
    def traced_task():
        return 1

    ray_tpu.get([traced_task.remote() for _ in range(3)])
    time.sleep(1.5)   # event flush interval
    tasks = state.list_tasks()
    mine = [t for t in tasks if t.get("name") == "traced_task"]
    assert len(mine) == 3
    assert all(t["state"] == "FINISHED" for t in mine)
    summ = state.summarize_tasks()
    assert summ.get("traced_task", {}).get("FINISHED") == 3


def test_list_actors(ray_start):
    @ray_tpu.remote
    class Tracked:
        def ping(self):
            return 1

    a = Tracked.remote()
    ray_tpu.get(a.ping.remote())
    actors = state.list_actors()
    assert any(x["state"] == "ALIVE" for x in actors)
    assert state.summarize_actors().get("ALIVE", 0) >= 1


def test_job_submission(ray_start):
    from ray_tpu.job_submission import JobSubmissionClient
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"print('job says hi')\"")
    status = client.wait_until_finished(job_id, timeout=60)
    assert status == "SUCCEEDED"
    assert "job says hi" in client.get_job_logs(job_id)
    jobs = client.list_jobs()
    assert any(j["job_id"] == job_id for j in jobs)


def test_job_failure_status(ray_start):
    from ray_tpu.job_submission import JobSubmissionClient
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"import sys; sys.exit(3)\"")
    status = client.wait_until_finished(job_id, timeout=60)
    assert status == "FAILED"


def test_dashboard_rest(ray_start):
    from ray_tpu.dashboard import start_dashboard
    start_dashboard(port=18266)

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:18266{path}", timeout=30) as r:
            return json.loads(r.read())

    nodes = get("/api/nodes")
    assert len(nodes) == 1
    st = get("/api/cluster_status")
    assert st["nodes_alive"] == 1
    # submit a job over REST
    req = urllib.request.Request(
        "http://127.0.0.1:18266/api/jobs",
        data=json.dumps({"entrypoint":
                         f"{sys.executable} -c \"print('rest job')\""}
                        ).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        job_id = json.loads(r.read())["job_id"]
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        info = get(f"/api/jobs/{job_id}")
        if info["status"] in ("SUCCEEDED", "FAILED"):
            break
        time.sleep(0.5)
    assert info["status"] == "SUCCEEDED"
    assert "rest job" in get(f"/api/jobs/{job_id}/logs")["logs"]


def test_user_metrics_and_prometheus(ray_start):
    """Counter/Gauge/Histogram push to GCS; /metrics renders Prometheus
    text (reference: ray.util.metrics + metrics agent export)."""
    import time

    import ray_tpu
    from ray_tpu.util.metrics import (Counter, Gauge, Histogram,
                                      render_prometheus)

    c = Counter("test_requests_total", "reqs", tag_keys=("route",))
    c.inc(3, tags={"route": "/a"})
    c.inc(2, tags={"route": "/b"})
    g = Gauge("test_queue_depth", "depth")
    g.set(7)
    h = Histogram("test_latency_s", "lat", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)

    # the GCS ingests its own gcs_* rows too, so a non-empty snapshot
    # is not yet this driver's push: wait for the driver's own rows
    deadline = time.time() + 60
    text = ""
    while time.time() < deadline:
        text = render_prometheus(
            ray_tpu._get_worker().gcs_call("get_metrics") or {})
        if "test_latency_s_count" in text:
            break
        time.sleep(0.5)
    assert 'test_requests_total{route="/a"} 3.0' in text
    assert "test_queue_depth 7.0" in text
    assert 'test_latency_s_bucket{le="0.1"} 1' in text
    assert "test_latency_s_count 3" in text


def test_worker_logs_reach_driver(ray_start, capfd):
    """print() inside a task is echoed to the driver with a (pid, ip)
    prefix (reference: log_monitor -> pubsub -> driver stdout)."""
    import time

    import ray_tpu

    @ray_tpu.remote
    def chatty():
        print("hello-from-worker-xyz", flush=True)
        return 1

    assert ray_tpu.get(chatty.remote(), timeout=60) == 1
    deadline = time.time() + 10
    seen = False
    while time.time() < deadline and not seen:
        time.sleep(0.7)
        out = capfd.readouterr().out
        seen = "hello-from-worker-xyz" in out
    assert seen, "worker stdout never reached the driver"


def test_dashboard_index_and_timeline(ray_start, tmp_path):
    """Dashboard serves a UI page; the timeline exporter produces a
    chrome trace (reference: dashboard frontend, `ray timeline`)."""
    from ray_tpu.dashboard import start_dashboard
    start_dashboard(port=18266)   # reuses the detached dashboard actor

    @ray_tpu.remote
    def traced_task(x):
        return x + 1

    assert ray_tpu.get(traced_task.remote(1), timeout=30) == 2
    with urllib.request.urlopen(
            "http://127.0.0.1:18266/", timeout=10) as resp:
        body = resp.read().decode()
    assert "ray_tpu dashboard" in body and "/api/" in body

    time.sleep(2.0)        # task-event buffers flush every 1s
    out = str(tmp_path / "trace.json")
    ray_tpu.timeline(out)
    trace = json.loads(open(out).read())
    assert isinstance(trace, list) and trace
    assert any(ev.get("name") == "traced_task" for ev in trace)


def test_list_objects_reports_sizes(ray_start):
    import numpy as np
    ref = ray_tpu.put(np.ones(300_000, dtype=np.uint8))
    rows = state.list_objects()
    shm = [r for r in rows if r.get("kind", "").endswith("shm")]
    assert shm and any(r["size_bytes"] >= 300_000 for r in shm)
    owned = [r for r in rows if "owned" in r.get("kind", "")]
    assert any(r["object_id"] == ref.id.hex() for r in owned)
    del ref


def test_list_objects_respects_limit_and_dedupes(ray_start):
    """An object both shm-resident and owned collapses to ONE
    'owned+shm' row (carrying size AND ownership fields), and the
    result never exceeds `limit` rows."""
    import numpy as np
    refs = [ray_tpu.put(np.ones(200_000, dtype=np.uint8))
            for _ in range(6)]
    rows = state.list_objects()
    ids = [r["object_id"] for r in rows]
    assert len(ids) == len(set(ids)), "duplicate rows for one object"
    merged = {r["object_id"]: r for r in rows}
    for ref in refs:
        row = merged[ref.id.hex()]
        assert row["kind"] == "owned+shm"
        assert row["size_bytes"] >= 200_000
        assert "complete" in row and "borrowers" in row
    assert len(state.list_objects(limit=3)) <= 3
    del refs
