"""Parity utilities: ActorPool, Queue, cancel, runtime_env, timeline,
workflow, spilling, autoscaler (reference: python/ray/tests/test_actor_pool,
test_queue, test_cancel, test_runtime_env, workflow tests, autoscaler
fake-provider tests)."""

import os
import time

import pytest

import ray_tpu
from ray_tpu.util.actor_pool import ActorPool
from ray_tpu.util.queue import Queue


RAY_START = dict(num_cpus=4, object_store_memory=64 * 1024 * 1024)


def test_actor_pool(ray_start):
    @ray_tpu.remote
    class Sq:
        def sq(self, x):
            return x * x

    pool = ActorPool([Sq.remote() for _ in range(2)])
    out = list(pool.map(lambda a, v: a.sq.remote(v), range(8)))
    assert out == [i * i for i in range(8)]
    out2 = sorted(pool.map_unordered(lambda a, v: a.sq.remote(v), range(5)))
    assert out2 == [i * i for i in range(5)]


def test_queue(ray_start):
    q = Queue(maxsize=3)
    for i in range(3):
        q.put(i)
    assert q.qsize() == 3
    with pytest.raises(Exception):
        q.put(99, block=False)
    assert [q.get() for _ in range(3)] == [0, 1, 2]
    with pytest.raises(Exception):
        q.get(block=False)

    q2 = Queue()   # unbounded: producer must not block on a full queue
    @ray_tpu.remote
    def producer(q):
        for i in range(5):
            q.put(i * 10)

    ray_tpu.get(producer.remote(q2), timeout=30)
    assert [q2.get(timeout=10) for _ in range(5)] == [0, 10, 20, 30, 40]


def test_cancel_queued_task(ray_start):
    @ray_tpu.remote
    def blocker():
        import time
        time.sleep(5)
        return "done"

    @ray_tpu.remote
    def victim():
        return "ran"

    # fill all 4 CPUs, then queue a victim and cancel it
    blockers = [blocker.remote() for _ in range(4)]
    time.sleep(0.5)
    v = victim.remote()
    time.sleep(0.3)
    ray_tpu.cancel(v)
    with pytest.raises((ray_tpu.TaskCancelledError, Exception)):
        ray_tpu.get(v, timeout=30)
    assert ray_tpu.get(blockers, timeout=30) == ["done"] * 4


def test_runtime_env_env_vars(ray_start):
    @ray_tpu.remote(runtime_env={"env_vars": {"MY_FLAG": "yes_hello"}})
    def read_env():
        import os
        return os.environ.get("MY_FLAG")

    assert ray_tpu.get(read_env.remote()) == "yes_hello"

    @ray_tpu.remote
    def read_env2():
        import os
        return os.environ.get("MY_FLAG")

    assert ray_tpu.get(read_env2.remote()) is None   # restored after task


def test_timeline_export(ray_start, tmp_path):
    @ray_tpu.remote
    def traced():
        return 1

    ray_tpu.get([traced.remote() for _ in range(2)])
    time.sleep(1.5)
    out = str(tmp_path / "trace.json")
    ray_tpu.timeline(out)
    import json
    with open(out) as f:
        events = json.load(f)
    assert any(e["name"] == "traced" for e in events)


def test_workflow_resume(ray_start, tmp_path):
    from ray_tpu import workflow

    counter_file = str(tmp_path / "exec_count")

    def bump_counter():
        n = int(open(counter_file).read()) if os.path.exists(counter_file) \
            else 0
        with open(counter_file, "w") as f:
            f.write(str(n + 1))

    @workflow.step
    def load():
        bump_counter()
        return 10

    @workflow.step
    def double(x):
        return x * 2

    @workflow.step
    def add(a, b):
        return a + b

    dag = add.bind(double.bind(load.bind()), load.bind())
    out = workflow.run(dag, workflow_id="wf1", storage=str(tmp_path / "wf"))
    assert out == 30
    runs_first = int(open(counter_file).read())
    # resume: all steps checkpointed, nothing re-executes
    out2 = workflow.run(dag, workflow_id="wf1", storage=str(tmp_path / "wf"))
    assert out2 == 30
    assert int(open(counter_file).read()) == runs_first
    workflow.delete("wf1", storage=str(tmp_path / "wf"))


def test_object_spill_and_restore(ray_start):
    """Fill the 64MB store past its spill threshold; earlier objects spill
    to disk and must still be readable."""
    import numpy as np
    refs = [ray_tpu.put(np.full(8 * 1024 * 1024 // 8, i, np.float64))
            for i in range(12)]   # 96MB total in a 64MB store
    time.sleep(5)   # spill loop cadence
    for i, r in enumerate(refs):
        arr = ray_tpu.get(r, timeout=30)
        assert arr[0] == i, f"object {i} corrupted/lost"


def test_runtime_env_working_dir_and_py_modules(ray_start, tmp_path):
    """Local dirs ship as content-addressed zips through the GCS KV and
    materialize on workers (reference: runtime-env packaging — GCS zips,
    packaging.py; URI-cached)."""
    wd = tmp_path / "proj"
    wd.mkdir()
    (wd / "data.txt").write_text("payload-42")
    mod = tmp_path / "mylib"
    mod.mkdir()
    (mod / "__init__.py").write_text("MAGIC = 1234\n")

    @ray_tpu.remote(runtime_env={"working_dir": str(wd),
                                 "py_modules": [str(mod)]})
    def probe():
        import os
        with open("data.txt") as f:
            content = f.read()
        import mylib
        return content, mylib.MAGIC, os.getcwd()

    content, magic, cwd = ray_tpu.get(probe.remote(), timeout=60)
    assert content == "payload-42"
    assert magic == 1234
    # ran in the EXTRACTED copy, not the source dir
    assert cwd != str(wd) and "runtime_envs" in cwd


def test_multiprocessing_pool(ray_start):
    """Pool shim (reference: ray.util.multiprocessing.Pool)."""
    from ray_tpu.util.multiprocessing import Pool

    def sq(x):
        return x * x

    def addmul(a, b):
        return a * 10 + b

    with Pool(processes=2) as p:
        assert p.map(sq, range(8)) == [i * i for i in range(8)]
        assert p.apply(sq, (7,)) == 49
        ar = p.apply_async(sq, (9,))
        assert ar.get(timeout=30) == 81 and ar.successful()
        assert list(p.imap(sq, range(4))) == [0, 1, 4, 9]
        assert sorted(p.imap_unordered(sq, range(4))) == [0, 1, 4, 9]
        assert p.starmap(addmul, [(1, 2), (3, 4)]) == [12, 34]


def test_workflow_events_and_virtual_actors(ray_start, tmp_path):
    """Workflow event steps block durably until send_event; virtual
    actors persist state per method call (reference: ray.workflow events
    + virtual actors)."""
    import threading

    from ray_tpu import workflow

    @workflow.step
    def before():
        return "ready"

    @workflow.step
    def combine(a, ev):
        return f"{a}:{ev}"

    node = combine.bind(before.bind(), workflow.wait_for_event("go"))

    out = {}

    def runner():
        out["v"] = workflow.run(node, workflow_id="ev-wf",
                                storage=str(tmp_path))

    t = threading.Thread(target=runner)
    t.start()
    time.sleep(0.5)
    assert t.is_alive()            # blocked on the event
    workflow.send_event("ev-wf", "go", "signal", storage=str(tmp_path))
    t.join(timeout=60)
    assert out["v"] == "ready:signal"
    # resume consumes the checkpoint, not the event again
    assert workflow.run(node, workflow_id="ev-wf",
                        storage=str(tmp_path)) == "ready:signal"

    class Counter:
        def __init__(self):
            self.n = 0

        def add(self, k):
            self.n += k
            return self.n

    a = workflow.get_actor(Counter, "c1", storage=str(tmp_path))
    assert a.add(2) == 2
    assert a.add(3) == 5
    # a fresh handle (fresh process in real life) sees durable state
    b = workflow.get_actor(Counter, "c1", storage=str(tmp_path))
    assert b.add(1) == 6
