"""Mutable-channel + compiled-DAG tests (reference:
python/ray/dag/tests/experimental/test_accelerated_dag.py shapes)."""

import multiprocessing
import os
import time

import ray_tpu
from ray_tpu.dag import InputNode, MultiOutputNode
from ray_tpu.experimental.channel import Channel, ChannelClosed


RAY_START = dict(num_cpus=6, object_store_memory=128 * 1024 * 1024)


def test_channel_same_process():
    path = f"/dev/shm/rt_test_chan1_{os.getpid()}"
    ch = Channel(path, max_size=1 << 16, num_readers=1, create=True)
    reader = Channel(path)
    ch.write({"x": 1})
    assert reader.read() == {"x": 1}
    ch.write([1, 2, 3])
    assert reader.read() == [1, 2, 3]
    ch.destroy()


def _reader_proc(path, out_q):
    ch = Channel(path)
    vals = []
    try:
        while True:
            vals.append(ch.read(timeout_s=10))
    except ChannelClosed:
        pass
    out_q.put(vals)


def test_channel_cross_process_backpressure():
    path = f"/dev/shm/rt_test_chan2_{os.getpid()}"
    ch = Channel(path, max_size=1 << 16, num_readers=1, create=True)
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_reader_proc, args=(path, q))
    p.start()
    try:
        for i in range(20):
            ch.write(i)     # blocks until reader acks previous version
        ch.close()
        vals = q.get(timeout=60)
        p.join(timeout=30)
        assert vals == list(range(20))   # every version seen exactly once
    finally:
        if p.is_alive():
            p.kill()
        ch.destroy()


def test_compiled_dag_linear(ray_start):
    @ray_tpu.remote
    class AddOne:
        def add(self, x):
            return x + 1

    @ray_tpu.remote
    class Double:
        def mul(self, x):
            return x * 2

    a, b = AddOne.remote(), Double.remote()
    with InputNode() as inp:
        mid = a.add.bind(inp)
        out = b.mul.bind(mid)
    dag = out.experimental_compile()
    try:
        assert dag.execute(5) == 12
        assert dag.execute(10) == 22
        # repeated execution is the point: run many
        for i in range(50):
            assert dag.execute(i) == (i + 1) * 2
    finally:
        dag.teardown()


def test_compiled_dag_multi_output(ray_start):
    @ray_tpu.remote
    class Worker1:
        def inc(self, x):
            return x + 1

        def dec(self, x):
            return x - 1

    w1, w2 = Worker1.remote(), Worker1.remote()
    with InputNode() as inp:
        o1 = w1.inc.bind(inp)
        o2 = w2.dec.bind(inp)
        dag = MultiOutputNode([o1, o2]).experimental_compile()
    try:
        assert dag.execute(10) == [11, 9]
    finally:
        dag.teardown()


def test_compiled_dag_throughput(ray_start):
    @ray_tpu.remote
    class Echo:
        def ping(self, x):
            return x

    e = Echo.remote()
    with InputNode() as inp:
        dag = e.ping.bind(inp).experimental_compile()
    try:
        for _ in range(5):
            dag.execute(0)
        n = 200
        t0 = time.perf_counter()
        for i in range(n):
            dag.execute(i)
        dt = time.perf_counter() - t0
        per_call_us = dt / n * 1e6
        # must be far below the RPC path (~1ms); expect tens of µs
        assert per_call_us < 2000, per_call_us
    finally:
        dag.teardown()
