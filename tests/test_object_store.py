"""Tests for the native shared-memory object store.

Mirrors the coverage themes of the reference's plasma tests
(reference: src/ray/object_manager/plasma/ test suite): create/seal/get,
zero-copy reads, eviction under pressure, deferred delete, multi-process
visibility — plus the lock-striped arena paths: multi-process put/get
contention across stripes, round-robin fallback off a full home stripe,
and robust-mutex repair after a client is SIGKILLed mid-``rt_create``.
"""

import multiprocessing
import os
import time

import numpy as np
import pytest

# the store's zero-copy pin lifetime rides the PEP 688 __buffer__
# protocol — the whole module is 3.12-gated through this import
_object_store = pytest.importorskip(
    "ray_tpu._private.object_store", reason="object store requires 3.12")
ObjectStoreClient = _object_store.ObjectStoreClient

from ray_tpu.util.chaos import ShmCreateKiller  # noqa: E402


@pytest.fixture()
def store(tmp_path):
    path = "/dev/shm/raytpu_test_%d" % os.getpid()
    s = ObjectStoreClient(path, create=True, size=64 * 1024 * 1024)
    yield s
    s.close()
    os.unlink(path)


def oid(n: int) -> bytes:
    return n.to_bytes(20, "big")


def test_put_get_roundtrip(store):
    payload = b"hello world" * 1000
    assert store.put_bytes(oid(1), payload, metadata=b"meta")
    buf = store.get(oid(1))
    assert bytes(buf.data) == payload
    assert buf.metadata == b"meta"
    assert store.contains(oid(1))
    assert store.get(oid(2)) is None


def test_zero_copy_numpy(store):
    arr = np.arange(100000, dtype=np.float32)
    store.put_bytes(oid(3), arr.tobytes())
    buf = store.get(oid(3))
    out = np.frombuffer(buf.data, dtype=np.float32)
    np.testing.assert_array_equal(out, arr)


def test_duplicate_create(store):
    assert store.put_bytes(oid(4), b"x")
    assert not store.put_bytes(oid(4), b"y")


def test_create_write_seal(store):
    data, meta = store.create(oid(5), 8, 2)
    data[:] = b"abcdefgh"
    meta[:] = b"mm"
    # not visible until sealed
    assert not store.contains(oid(5))
    store.seal(oid(5))
    buf = store.get(oid(5))
    assert bytes(buf.data) == b"abcdefgh"
    assert buf.metadata == b"mm"


def test_delete_and_deferred_delete(store):
    store.put_bytes(oid(6), b"z" * 100)
    buf = store.get(oid(6))  # pinned
    store.delete(oid(6))
    # still readable through existing pin's view
    assert bytes(buf.data) == b"z" * 100
    buf.close()
    assert not store.contains(oid(6))


def test_lru_eviction(store):
    # Fill most of the 64 MiB arena with 8 MiB objects, then allocate more:
    # oldest unpinned objects must be evicted.
    blob = b"\x01" * (8 * 1024 * 1024)
    for i in range(10, 20):
        store.put_bytes(oid(i), blob)
    stats = store.stats()
    assert stats["num_evictions"] >= 1
    # most recent object is resident
    assert store.contains(oid(19))


def test_pinned_objects_not_evicted(store):
    blob = b"\x02" * (8 * 1024 * 1024)
    store.put_bytes(oid(20), blob)
    pin = store.get(oid(20))
    for i in range(21, 30):
        store.put_bytes(oid(i), blob)
    assert store.contains(oid(20))
    assert bytes(pin.data[:4]) == b"\x02\x02\x02\x02"
    pin.close()


def test_abort(store):
    store.create(oid(30), 1024)
    store.abort(oid(30))
    assert not store.contains(oid(30))
    # space reusable
    assert store.put_bytes(oid(30), b"done")


def _child_read(path, key, expected):
    c = ObjectStoreClient(path)
    buf = c.get(key)
    assert buf is not None and bytes(buf.data) == expected
    c.put_bytes(b"\x99" * 20, b"from-child")
    c.close()


def test_multiprocess_visibility(store):
    store.put_bytes(oid(40), b"shared-payload")
    ctx = multiprocessing.get_context("spawn")
    p = ctx.Process(target=_child_read, args=(store.path, oid(40), b"shared-payload"))
    p.start()
    try:
        p.join(60)
        assert p.exitcode == 0
    finally:
        if p.is_alive():
            p.kill()
    buf = store.get(b"\x99" * 20)
    assert bytes(buf.data) == b"from-child"


def test_stats(store):
    store.put_bytes(oid(50), b"x" * 1000)
    st = store.stats()
    assert st["num_objects"] >= 1
    assert st["bytes_in_use"] >= 1000
    assert st["capacity"] > 0


def test_many_small_objects(store):
    for i in range(2000):
        store.put_bytes(oid(1000 + i), i.to_bytes(4, "big"))
    for i in range(0, 2000, 97):
        buf = store.get(oid(1000 + i))
        assert int.from_bytes(bytes(buf.data), "big") == i


# ---------------------------------------------------- lock-striped arena


@pytest.fixture()
def striped_store():
    path = "/dev/shm/raytpu_test_striped_%d" % os.getpid()
    s = ObjectStoreClient(path, create=True, size=64 * 1024 * 1024,
                          stripes=4)
    yield s
    s.close()
    os.unlink(path)


def _home_stripe(oid_bytes: bytes, nstripes: int) -> int:
    """Python mirror of hash_id/stripe_of in shm_store.cpp (test-only:
    used to construct deterministic stripe collisions; drift between the
    two shows up as test_stripe_fallback failing to provoke one)."""
    mask = (1 << 64) - 1
    a = int.from_bytes(oid_bytes[0:8], "little")
    b = int.from_bytes(oid_bytes[8:16], "little")
    c = int.from_bytes(oid_bytes[16:20], "little")
    h = a ^ ((b * 0x9E3779B97F4A7C15) & mask) ^ ((c << 17) & mask)
    h ^= h >> 33
    h = (h * 0xFF51AFD7ED558CCD) & mask
    h ^= h >> 33
    return (h >> 40) % nstripes


def test_striped_roundtrip_and_stats(striped_store):
    s = striped_store
    assert s.num_stripes() == 4
    for i in range(200):
        assert s.put_bytes(oid(5000 + i), i.to_bytes(8, "big"))
    for i in range(200):
        buf = s.get(oid(5000 + i))
        assert int.from_bytes(bytes(buf.data), "big") == i
    st = s.stats()
    assert st["num_stripes"] == 4
    assert st["num_objects"] >= 200
    assert st["poisoned"] == 0
    # per-stripe accounting sums to the aggregate
    per = [s.stripe_stats(i) for i in range(4)]
    assert sum(p["bytes_in_use"] for p in per) == st["bytes_in_use"]
    assert sum(p["capacity"] for p in per) == st["capacity"]
    # the id hash actually spreads objects over several stripes
    assert sum(1 for p in per if p["num_objects"] > 0) >= 2


def test_stripe_fallback_when_home_full(striped_store):
    s = striped_store
    # two ids with the SAME home stripe; each object fills >half a
    # 16 MiB stripe, so the second create cannot fit at home and must
    # re-home round-robin — while the first stays pinned (unevictable).
    ids = []
    n = 0
    while len(ids) < 2:
        cand = oid(42000 + n)
        n += 1
        if not ids or _home_stripe(cand, 4) == _home_stripe(ids[0], 4):
            ids.append(cand)
    big = (64 * 1024 * 1024 // 4) * 6 // 10
    pins = []
    for i in ids:
        assert s.put_bytes(i, b"\x11" * big)
        pins.append(s.get(i))
    assert s.stats()["create_fallbacks"] >= 1
    for i, pin in zip(ids, pins):
        assert s.contains(i)
        pin.close()
        s.delete(i)


def _contend_worker(path, duration, seed, q):
    c = ObjectStoreClient(path)
    payload = b"\xcd" * (4 * 1024 * 1024)
    n, errors = 0, 0
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < duration:
        key = (seed * 1_000_000 + i).to_bytes(20, "big")
        i += 1
        try:
            if not c.put_bytes(key, payload):
                errors += 1
            buf = c.get(key)
            if buf is None:
                errors += 1
            else:
                buf.close()
            c.delete(key)
            n += 1
        except Exception:
            errors += 1
    dt = time.perf_counter() - t0
    q.put((n * len(payload) / dt, errors))
    c.close()


def test_multiprocess_put_contention():
    """ISSUE 6 acceptance: N put/get clients against one striped arena
    must aggregate at least the single-client rate (on a multi-core box;
    a 1-core host can only time-slice) with zero seal/create errors."""
    path = "/dev/shm/raytpu_test_contend_%d" % os.getpid()
    s = ObjectStoreClient(path, create=True, size=256 * 1024 * 1024,
                          stripes=4)
    ctx = multiprocessing.get_context("spawn")
    try:
        duration = 0.8

        def run(n_clients, seed0):
            q = ctx.Queue()
            procs = [ctx.Process(target=_contend_worker,
                                 args=(path, duration, seed0 + k, q))
                     for k in range(n_clients)]
            try:
                for p in procs:
                    p.start()
                results = [q.get(timeout=120) for _ in procs]
                for p in procs:
                    p.join(30)
                    assert p.exitcode == 0
            finally:
                for p in procs:
                    if p.is_alive():
                        p.kill()
            return results

        single = run(1, seed0=10)
        multi = run(4, seed0=20)
        single_rate = single[0][0]
        agg = sum(r for r, _ in multi)
        errors = single[0][1] + sum(e for _, e in multi)
        assert errors == 0, f"{errors} put/get client errors"
        min_ratio = 1.0 if (os.cpu_count() or 1) >= 2 else 0.5
        assert agg >= single_rate * min_ratio, \
            (agg, single_rate, [r for r, _ in multi])
        assert s.stats()["poisoned"] == 0
    finally:
        s.close()
        os.unlink(path)


def _chaos_put_loop(path, spec):
    # arm BEFORE the first native create: the spec is parsed once per
    # process (spawn context => fresh interpreter => fresh parse)
    os.environ[ShmCreateKiller.SPEC_ENV] = spec
    from ray_tpu._private.object_store import ObjectStoreClient as Client
    c = Client(path)
    for i in range(1000):
        try:
            c.put_bytes((7_000_000 + i).to_bytes(20, "big"), b"\xab" * 4096)
        except Exception:
            pass
    os._exit(3)  # survived 1000 puts: the injection never fired


def test_kill_mid_create_repairs_stripe(striped_store):
    """Robust-mutex chaos: a client SIGKILLed inside rt_create while
    holding a stripe mutex must not take the store down — survivors hit
    EOWNERDEAD, repair the poisoned stripe, and keep serving puts."""
    s = striped_store
    for i in range(8):
        assert s.put_bytes(oid(60000 + i), b"\x22" * 1024)
    killer = ShmCreateKiller(nth_create=3)
    ctx = multiprocessing.get_context("spawn")
    victim = ctx.Process(target=_chaos_put_loop,
                         args=(s.path, killer.spec()))
    victim.start()
    try:
        killer.assert_killed(victim)
    finally:
        if victim.is_alive():
            victim.kill()
    # stats() itself walks every stripe (seqlock -> locked fallback on the
    # stuck one), so the first poll performs the EOWNERDEAD repair
    st = s.stats()
    assert st["stripe_repairs"] >= 1
    assert st["poisoned"] == 0
    # and the arena keeps serving puts on every stripe
    for i in range(64):
        assert s.put_bytes(oid(70000 + i), b"\x33" * 2048)
        buf = s.get(oid(70000 + i))
        assert bytes(buf.data) == b"\x33" * 2048
        buf.close()
    assert s.stats()["poisoned"] == 0


# ------------------------------------------------- spanning allocation
# Objects larger than one stripe (64 MiB arena / 4 stripes = 16 MiB)
# route to the spanning path: contiguous whole stripes, one descriptor,
# whole-span eviction/repair. ISSUE 11 acceptance: put/get/pin/evict/
# crash-repair above one stripe size.

from ray_tpu.util.chaos import ShmSpanCreateKiller  # noqa: E402


def test_spanning_put_get_roundtrip(striped_store):
    s = striped_store
    blob = bytes(range(256)) * (20 * 1024 * 1024 // 256)   # 20 MiB
    assert len(blob) > s.max_alloc_bytes()
    assert s.put_bytes(oid(80001), blob, metadata=b"span-meta")
    assert s.is_span(oid(80001))
    assert s.contains(oid(80001))
    buf = s.get(oid(80001))
    assert bytes(buf.data) == blob
    assert buf.metadata == b"span-meta"
    st = s.stats()
    assert st["num_spans"] == 1
    assert st["span_creates"] >= 1
    sp = s.span_stats()
    assert sp["live_spans"] == 1
    assert sp["stripes_claimed"] == 2      # 20 MiB over 16 MiB stripes
    assert sp["span_bytes"] == len(blob) + len(b"span-meta")
    buf.close()
    s.delete(oid(80001))
    assert not s.contains(oid(80001))
    assert s.span_stats()["stripes_claimed"] == 0   # whole span returned


def test_spanning_zero_copy_numpy(striped_store):
    s = striped_store
    arr = np.arange(5 * 1024 * 1024, dtype=np.float32)     # 20 MiB
    s.put_bytes(oid(80002), arr.tobytes())
    buf = s.get(oid(80002))
    out = np.frombuffer(buf.data, dtype=np.float32)
    np.testing.assert_array_equal(out, arr)
    buf.close()


def test_spanning_pin_survives_lru_pressure(striped_store):
    """LRU pressure never half-frees a span: normal creates evict
    AROUND a pinned span; the span's bytes stay intact throughout."""
    s = striped_store
    blob = b"\x5a" * (20 * 1024 * 1024)
    assert s.put_bytes(oid(80010), blob)
    pin = s.get(oid(80010))
    # hammer well past the remaining two stripes' capacity
    for i in range(24):
        s.put_bytes(oid(80100 + i), b"\x11" * (4 * 1024 * 1024))
    assert s.contains(oid(80010))
    sp = s.span_stats()
    assert sp["live_spans"] == 1 and sp["stripes_claimed"] == 2
    assert bytes(pin.data[:8]) == b"\x5a" * 8
    assert bytes(pin.data[-8:]) == b"\x5a" * 8
    pin.close()
    s.delete(oid(80010))


def test_spanning_eviction_is_atomic(striped_store):
    """An unpinned sealed span is reclaimed WHOLE under pressure, and
    its stripes rejoin the normal allocator."""
    s = striped_store
    assert s.put_bytes(oid(80020), b"\x66" * (20 * 1024 * 1024))
    assert s.is_span(oid(80020))
    freed = s.evict(64 * 1024 * 1024)
    assert freed >= 20 * 1024 * 1024
    assert not s.contains(oid(80020))
    sp = s.span_stats()
    assert sp["live_spans"] == 0 and sp["stripes_claimed"] == 0
    assert sp["span_evictions"] >= 1
    assert s.stats()["num_spans"] == 0
    # reclaimed stripes serve normal puts again
    for i in range(8):
        assert s.put_bytes(oid(80200 + i), b"\x44" * (1024 * 1024))


def test_create_spanning_forced_and_abort(striped_store):
    """rt_create_spanning exercises span machinery with small objects;
    abort of an unsealed span returns every claimed stripe."""
    s = striped_store
    bufs = s.create_spanning(oid(80030), 4096, 4)
    assert bufs is not None
    data, meta = bufs
    data[:] = b"\x77" * 4096
    meta[:] = b"mm.."
    assert s.is_span(oid(80030))
    assert not s.contains(oid(80030))       # unsealed: not visible
    s.abort(oid(80030))
    assert not s.is_span(oid(80030))
    assert s.span_stats()["stripes_claimed"] == 0
    # duplicate detection across planes: a sealed span blocks a normal
    # create of the same id
    assert s.create_spanning(oid(80031), 1024) is not None
    s.seal(oid(80031))
    assert s.create(oid(80031), 64) is None
    s.delete(oid(80031))


def test_max_alloc_boundary_routes_exactly(striped_store):
    s = striped_store
    cap = s.max_alloc_bytes()
    assert s.put_bytes(oid(80040), b"a" * cap)
    assert not s.is_span(oid(80040))        # fits one stripe: normal path
    s.delete(oid(80040))
    assert s.put_bytes(oid(80041), b"b" * (cap + 1))
    assert s.is_span(oid(80041))            # one byte over: spanning path
    s.delete(oid(80041))


def _chaos_span_loop(path, spec):
    # arm BEFORE the first native create (spec parsed once per process)
    os.environ[ShmSpanCreateKiller.SPEC_ENV] = spec
    from ray_tpu._private.object_store import ObjectStoreClient as Client
    c = Client(path)
    try:
        c.create_spanning((8_500_000).to_bytes(20, "big"),
                          20 * 1024 * 1024, 0)
    except Exception:
        pass
    os._exit(3)  # survived the spanning create: the injection never fired


def test_kill_mid_spanning_create_repairs_whole_span(striped_store):
    """ISSUE 11 chaos: a client SIGKILLed inside span_create — span
    mutex + a member stripe's mutex held, descriptor CLAIMING — must
    leave survivors able to free/invalidate the WHOLE half-claimed span
    and keep both allocation planes serving."""
    s = striped_store
    for i in range(8):
        assert s.put_bytes(oid(81000 + i), b"\x22" * 1024)
    killer = ShmSpanCreateKiller(nth_create=1)
    ctx = multiprocessing.get_context("spawn")
    victim = ctx.Process(target=_chaos_span_loop,
                         args=(s.path, killer.spec()))
    victim.start()
    try:
        killer.assert_killed(victim)
    finally:
        if victim.is_alive():
            victim.kill()
    # the gc sweep runs both repair levels (EOWNERDEAD on span mutex +
    # poisoned member stripe)
    s.gc_unsealed(0)
    sp = s.span_stats()
    assert sp["live_spans"] == 0
    assert sp["stripes_claimed"] == 0       # nothing half-claimed leaks
    assert sp["broken_slots"] == 0
    # both planes keep serving: a fresh span and fresh normal puts
    assert s.put_bytes(oid(81100), b"\x88" * (20 * 1024 * 1024))
    assert s.is_span(oid(81100))
    buf = s.get(oid(81100))
    assert bytes(buf.data[:4]) == b"\x88" * 4
    buf.close()
    s.delete(oid(81100))
    for i in range(16):
        assert s.put_bytes(oid(81200 + i), b"\x99" * 4096)
    st = s.stats()
    assert st["poisoned"] == 0
    assert st["span_repairs"] >= 1
