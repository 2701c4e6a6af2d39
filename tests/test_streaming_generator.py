"""Streaming generator returns (reference: num_returns="streaming" /
ObjectRefGenerator, python/ray/_raylet.pyx:281, item reporting protocol
core_worker.proto:400 ReportGeneratorItemReturns; tests modeled on
python/ray/tests/test_streaming_generator.py).
"""

import sys
import time

import numpy as np
import pytest

import ray_tpu


RAY_START = dict(num_cpus=2, object_store_memory=128 * 1024 * 1024)


def test_basic_stream(ray_start):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    g = gen.remote(5)
    assert isinstance(g, ray_tpu.ObjectRefGenerator)
    vals = [ray_tpu.get(ref) for ref in g]
    assert vals == [0, 10, 20, 30, 40]
    # the completion ref resolves to the item count
    assert ray_tpu.get(g.completed(), timeout=30) == 5


def test_items_arrive_before_completion(ray_start):
    """Consumers see early items while the producer is still running —
    the point of streaming vs. returning a list."""
    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        for i in range(3):
            yield i
            time.sleep(1.0)

    g = slow_gen.remote()
    t0 = time.monotonic()
    first = ray_tpu.get(g.next(timeout=30))
    dt = time.monotonic() - t0
    assert first == 0
    assert dt < 2.5, f"first item took {dt:.1f}s — buffered whole stream?"
    rest = [ray_tpu.get(r) for r in g]
    assert rest == [1, 2]


def test_backpressure_bounds_inflight(ray_start):
    """With backpressure K, an unconsumed stream holds <= K+1 items in
    flight; the producer advances only as the consumer acks."""
    K = 4

    @ray_tpu.remote(num_returns="streaming", _generator_backpressure=K)
    def counter(tmp):
        import pathlib
        for i in range(100):
            pathlib.Path(tmp).write_text(str(i + 1))
            yield np.ones(1024, np.uint8) * (i % 256)

    import tempfile
    with tempfile.NamedTemporaryFile() as f:
        g = counter.remote(f.name)
        time.sleep(3.0)     # producer runs free; consumer reads nothing
        produced = int(open(f.name).read())
        assert produced <= K + 1, \
            f"producer ran {produced} items ahead with K={K}"
        # consume everything; the stream completes
        n = sum(1 for _ in g)
        assert n == 100
        assert int(open(f.name).read()) == 100


def test_store_occupancy_stays_bounded(ray_start):
    """The verdict's acceptance shape: stream 100 shm-sized blocks with
    backpressure K and assert (via store stats) the object store never
    holds the whole stream — consumed-and-dropped items are freed by the
    owner while the producer keeps going."""
    K = 4
    BLOCK = 2 * 1024 * 1024

    @ray_tpu.remote(num_returns="streaming", _generator_backpressure=K)
    def blocks():
        for i in range(100):
            yield np.full(BLOCK, i % 256, np.uint8)

    w = ray_tpu._get_worker()
    base = w.node_call("get_node_info")["store"]["bytes_in_use"]
    g = blocks.remote()
    peak = 0
    n = 0
    for ref in g:
        arr = ray_tpu.get(ref)
        assert arr[0] == n % 256 and arr.nbytes == BLOCK
        n += 1
        del arr, ref
        if n % 10 == 0:
            used = w.node_call("get_node_info")["store"]["bytes_in_use"]
            peak = max(peak, used - base)
    assert n == 100
    # window K + consumer-held item + freeing slack; far below 100 blocks
    assert peak <= (2 * K + 4) * BLOCK, \
        f"store held {peak / BLOCK:.0f} blocks with K={K}"


def test_midstream_error_surfaces_in_order(ray_start):
    @ray_tpu.remote(num_returns="streaming")
    def bad():
        yield 1
        yield 2
        raise ValueError("boom at 3")

    g = bad.remote()
    assert ray_tpu.get(next(g)) == 1
    assert ray_tpu.get(next(g)) == 2
    with pytest.raises(Exception, match="boom"):
        ray_tpu.get(next(g))
    with pytest.raises(StopIteration):
        next(g)


def test_actor_streaming_method(ray_start):
    @ray_tpu.remote
    class Chunker:
        def stream(self, n):
            for i in range(n):
                yield f"chunk-{i}"

        def ping(self):
            return "pong"

    a = Chunker.remote()
    g = a.stream.options(num_returns="streaming").remote(4)
    assert [ray_tpu.get(r) for r in g] == [f"chunk-{i}" for i in range(4)]
    # the actor still answers ordinary calls afterwards
    assert ray_tpu.get(a.ping.remote(), timeout=30) == "pong"


def test_async_actor_streaming(ray_start):
    @ray_tpu.remote
    class AsyncGen:
        async def stream(self, n):
            import asyncio
            for i in range(n):
                await asyncio.sleep(0.01)
                yield i * i

    a = AsyncGen.remote()
    g = a.stream.options(num_returns="streaming").remote(5)
    assert [ray_tpu.get(r) for r in g] == [0, 1, 4, 9, 16]


def test_close_stops_producer(ray_start):
    @ray_tpu.remote(num_returns="streaming", _generator_backpressure=2)
    def forever(tmp):
        import pathlib
        i = 0
        while True:
            pathlib.Path(tmp).write_text(str(i))
            yield i
            i += 1

    import tempfile
    with tempfile.NamedTemporaryFile() as f:
        g = forever.remote(f.name)
        assert ray_tpu.get(next(g)) == 0
        g.close()
        time.sleep(1.0)
        after = int(open(f.name).read())
        time.sleep(2.0)
        assert int(open(f.name).read()) <= after + 2, \
            "producer kept running after close()"


def test_consumer_crash_cleans_up(ray_start):
    """A driver that dies mid-stream must not leave the producer
    running: the broken connection aborts the generator."""
    import subprocess
    import tempfile
    import textwrap
    with tempfile.NamedTemporaryFile() as f:
        addr = ray_tpu.get_gcs_address()
        script = textwrap.dedent(f"""
            import time
            import ray_tpu
            ray_tpu.init(address={addr!r})

            @ray_tpu.remote(num_returns="streaming",
                            _generator_backpressure=1000)
            def producer():
                import pathlib
                i = 0
                while True:
                    pathlib.Path({f.name!r}).write_text(str(i))
                    yield i
                    i += 1
                    time.sleep(0.01)

            g = producer.remote()
            ray_tpu.get(next(g))     # stream is live
            print("STREAMING", flush=True)
            time.sleep(600)
        """)
        proc = subprocess.Popen([sys.executable, "-c", script],
                                stdout=subprocess.PIPE, text=True)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if "STREAMING" in line:
                break
        proc.kill()
        proc.wait()
        def count():
            # write_text truncates, then writes: a read in between is ""
            while not (text := open(f.name).read()):
                time.sleep(0.001)
            return int(text)

        time.sleep(3.0)     # connection-loss detection + abort
        n1 = count()
        time.sleep(3.0)
        n2 = count()
        assert n2 <= n1 + 5, \
            f"producer still streaming after consumer death ({n1}->{n2})"
