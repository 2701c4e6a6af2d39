"""Off-loop put path: puts run entirely on the calling thread (caller-side
serialization + GIL-free chunked arena copies), so concurrent putters no
longer serialize behind the owner event loop.

Covers the three regressions the redesign could introduce:
  - corruption/loss under 4-thread concurrent large puts (owned-table and
    arena-allocator races),
  - a put issued from inside an actor while the worker's event loop is
    blocked (the old bridge would stall for the full block),
  - spilling under memory pressure still fires from the off-loop path.
"""

import os
import threading
import time
import zlib

import pytest

import numpy as np

import ray_tpu

BLOB = 8 * 1024 * 1024   # large enough for the shm + chunked-copy path


def _checksum(buf) -> int:
    return zlib.adler32(memoryview(buf))


@pytest.fixture
def ray_start():
    ctx = ray_tpu.init(num_cpus=2, object_store_memory=256 * 1024 * 1024)
    yield ctx
    ray_tpu.shutdown()


def test_concurrent_puts_no_corruption_and_faster_than_serial(ray_start):
    """4 threads put distinct large blobs concurrently: every get must
    hand back byte-identical data, and the concurrent phase must not be
    slower than the same work serialized through one thread (pre-change,
    every put funneled through the one event loop with the GIL held, so
    threads could only queue)."""
    n_threads, per_thread = 4, 4
    blobs = {t: np.full(BLOB, t + 1, np.uint8) for t in range(n_threads)}
    sums = {t: _checksum(blobs[t]) for t in range(n_threads)}

    # serial baseline: same total number of puts from one thread
    t0 = time.perf_counter()
    serial_refs = [ray_tpu.put(blobs[t % n_threads])
                   for t in range(n_threads * per_thread)]
    t_serial = time.perf_counter() - t0
    del serial_refs   # free arena space before the concurrent phase
    time.sleep(0.5)   # let the loop process the frees

    results: dict = {}
    errors: list = []

    def putter(t):
        try:
            results[t] = [ray_tpu.put(blobs[t]) for _ in range(per_thread)]
        except BaseException as e:   # noqa: BLE001 — surfaced to the test
            errors.append(e)

    threads = [threading.Thread(target=putter, args=(t,))
               for t in range(n_threads)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    t_concurrent = time.perf_counter() - t0
    assert not errors, errors
    assert all(not th.is_alive() for th in threads), "putter thread hung"

    # correctness first: every ref resolves to byte-identical data
    for t, refs in results.items():
        assert len(refs) == per_thread
        for r in refs:
            got = ray_tpu.get(r)
            assert got.nbytes == BLOB
            assert _checksum(got) == sums[t], f"thread {t} blob corrupted"

    # throughput: concurrent must beat the serialized baseline outright on
    # multi-core hosts; on a 1-core host the copies are memory-bound so we
    # only require the absence of a contention collapse
    bound = 1.0 if (os.cpu_count() or 1) >= 2 else 1.5
    assert t_concurrent < t_serial * bound, (
        f"concurrent 4-thread puts took {t_concurrent:.2f}s vs "
        f"{t_serial:.2f}s serialized (bound {bound}x) — puts are "
        "serializing again")


def test_put_from_inside_actor_while_loop_busy(ray_start):
    """A sync actor method puts a large object while the worker's own
    event loop is deliberately blocked: the put must complete without
    waiting for the loop (the old path bridged every put onto it)."""

    @ray_tpu.remote
    class Putter:
        def put_under_blocked_loop(self, block_s: float):
            from ray_tpu._private.worker import global_worker
            loop = global_worker.core.loop
            loop.call_soon_threadsafe(lambda: time.sleep(block_s))
            time.sleep(0.1)   # let the blocker occupy the loop
            arr = np.full(4 * 1024 * 1024, 7, np.uint8)
            t0 = time.perf_counter()
            ref = ray_tpu.put(arr)
            dt = time.perf_counter() - t0
            return ref, dt, _checksum(arr)

    a = Putter.remote()
    block_s = 2.0
    ref, dt, want = ray_tpu.get(
        a.put_under_blocked_loop.remote(block_s), timeout=60)
    assert dt < block_s / 2, (
        f"put inside the actor took {dt:.2f}s while the loop was blocked "
        f"for {block_s}s — it is bridging through the loop again")
    got = ray_tpu.get(ref, timeout=60)
    assert _checksum(got) == want


def test_put_spills_under_pressure_off_loop(tmp_path):
    """Memory-pressure regression for the caller-thread dispatch: filling
    the store past the watermark from a USER thread must still trigger
    the node manager's spill pass (the pressure check + blocking spill
    RPC moved off the loop with the rest of the put path)."""
    spill_uri = f"local://{tmp_path}/put-spill"
    os.environ["RAY_TPU_SPILL_URI"] = spill_uri
    try:
        ray_tpu.init(num_cpus=1, object_store_memory=64 * 1024 * 1024)
        blobs = [np.full(BLOB, i, np.uint8) for i in range(10)]
        refs = [ray_tpu.put(b) for b in blobs]    # 80 MB > 64 MB store
        deadline = time.time() + 30
        spilled = []
        root = str(tmp_path / "put-spill")
        while time.time() < deadline and not spilled:
            spilled = [f for _d, _s, fs in os.walk(root) for f in fs] \
                if os.path.isdir(root) else []
            time.sleep(0.5)
        assert spilled, "off-loop puts never triggered a spill pass"
        # every object still readable (restore path) and uncorrupted
        for i, r in enumerate(refs):
            got = ray_tpu.get(r, timeout=60)
            assert got.nbytes == BLOB
            assert _checksum(got) == _checksum(blobs[i])
    finally:
        os.environ.pop("RAY_TPU_SPILL_URI", None)
        ray_tpu.shutdown()
