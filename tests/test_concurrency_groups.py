"""Actor concurrency groups (reference:
src/ray/core_worker/transport/concurrency_group_manager.h + fibers —
named groups with independent concurrency limits; the default group
keeps its ordered single queue).

Here: per-group asyncio queue + consumer pool on the actor's worker;
methods declare their group with @ray_tpu.method(concurrency_group=...)
or per-call via .options(concurrency_group=...)."""

import time

import ray_tpu


RAY_START = dict(num_cpus=2, object_store_memory=128 * 1024 * 1024)


@ray_tpu.remote(concurrency_groups={"io": 2})
class Groups:
    def __init__(self):
        self.events = []

    def busy(self, t):
        self.events.append(("busy-start", time.monotonic()))
        time.sleep(t)
        self.events.append(("busy-end", time.monotonic()))
        return "busy"

    @ray_tpu.method(concurrency_group="io")
    def ping(self):
        self.events.append(("ping", time.monotonic()))
        return "pong"

    def get_events(self):
        return list(self.events)


def test_io_group_not_blocked_by_default_group(ray_start):
    """A long default-group call must NOT delay io-group methods — the
    whole point of groups (reference: concurrency groups keep health
    checks responsive behind busy user code)."""
    a = Groups.remote()
    ray_tpu.get(a.get_events.remote(), timeout=30)   # actor fully up
    slow = a.busy.remote(4.0)
    time.sleep(0.5)     # busy() is definitely running
    t0 = time.monotonic()
    assert ray_tpu.get(a.ping.remote(), timeout=10) == "pong"
    ping_latency = time.monotonic() - t0
    assert ping_latency < 2.0, \
        f"io-group ping waited {ping_latency:.1f}s behind default group"
    assert ray_tpu.get(slow, timeout=30) == "busy"


def test_per_call_group_override(ray_start):
    """.options(concurrency_group=...) routes a single call into a
    group, overriding the method's declared group."""
    a = Groups.remote()
    ray_tpu.get(a.get_events.remote(), timeout=30)   # actor fully up
    slow = a.busy.remote(3.0)
    time.sleep(0.3)
    t0 = time.monotonic()
    # get_events is default-group by declaration; route it via io
    ev = ray_tpu.get(
        a.get_events.options(concurrency_group="io").remote(), timeout=10)
    assert time.monotonic() - t0 < 2.0
    assert any(k == "busy-start" for k, _ in ev)
    ray_tpu.get(slow, timeout=30)


def test_group_width_limits_parallelism(ray_start):
    """The io group is 2-wide: three concurrent 1s io calls take ~2s
    (2 parallel + 1 queued), not ~1s or ~3s."""

    @ray_tpu.remote(concurrency_groups={"io": 2})
    class Width:
        @ray_tpu.method(concurrency_group="io")
        def io_sleep(self, t):
            time.sleep(t)
            return True

    a = Width.remote()
    ray_tpu.get(a.io_sleep.remote(0.01), timeout=30)   # warm worker
    t0 = time.monotonic()
    refs = [a.io_sleep.remote(1.0) for _ in range(3)]
    assert all(ray_tpu.get(refs, timeout=30))
    dt = time.monotonic() - t0
    assert 1.7 < dt < 3.4, f"3 x 1s on a 2-wide group took {dt:.2f}s"


def test_default_group_stays_ordered(ray_start):
    """Default-group calls from one submitter execute in order even when
    groups exist (the reference's ordered default group)."""

    @ray_tpu.remote(concurrency_groups={"io": 1})
    class Ordered:
        def __init__(self):
            self.seen = []

        def mark(self, i):
            self.seen.append(i)
            return i

        def get(self):
            return list(self.seen)

    a = Ordered.remote()
    refs = [a.mark.remote(i) for i in range(20)]
    ray_tpu.get(refs, timeout=30)
    assert ray_tpu.get(a.get.remote(), timeout=10) == list(range(20))
