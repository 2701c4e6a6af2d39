"""What the benchmark adds inside the replica's process.

`BenchLLMDeployment` is the program's `LLMDeployment`, unchanged in what
it serves, with the benchmark's instruments around it: the replica-side
clock of each request (entry of `__call__` to its first yield), the
engine's counters with the prefill compile count, the device's memory
statistic, the profiler's start and stop, and the plain reference run on
the replica's own weights. The worker imports this module by name: the
benchmark puts its checkout on the worker's PYTHONPATH.
"""

from __future__ import annotations

import threading
import time

from ray_tpu.inference import LLMDeployment


class BenchLLMDeployment(LLMDeployment):

    def __init__(self, model_kwargs: dict, config: dict, seed: int,
                 **engine):
        from perfbench import spec, weights
        family = spec.family_of(config)
        self._bench_config = config
        self._bench_timings = {}
        self._bench_lock = threading.Lock()
        model = family.build_model(model_kwargs)
        t0 = time.monotonic()
        super().__init__(model, seed=seed,
                         params_fn=lambda: weights.seeded_params(
                             model, seed, family.weight_rule),
                         weights_key=None, **engine)
        self._bench_init_s = time.monotonic() - t0
        from perfbench.runtime import watch_clock
        self._bench_clock_gaps = []
        threading.Thread(target=watch_clock, daemon=True, args=(
            threading.Event(), self._bench_clock_gaps)).start()

    def __call__(self, prompt_tokens, max_new_tokens: int = 64,
                 bench_id=None, **kw):
        t_entry = time.monotonic()
        inner = super().__call__(prompt_tokens, max_new_tokens, **kw)
        try:
            first = True
            for batch in inner:
                if first and bench_id is not None:
                    with self._bench_lock:
                        self._bench_timings[bench_id] = (
                            t_entry, time.monotonic())
                first = False
                yield batch
        finally:
            inner.close()

    # ------------------------------------------------------- instruments
    def bench_counters(self) -> dict:
        """The engine's counters, read through the handle before and
        after the window."""
        st = self.engine.stats()
        st["prefill_compile_count"] = self.engine.prefill_compile_count
        st["t_monotonic"] = time.monotonic()
        return st

    def bench_device(self) -> dict:
        import jax
        d = jax.devices()
        stats = d[0].memory_stats() or {}
        return {"platform": d[0].platform, "kind": d[0].device_kind,
                "count": len(d),
                "memory_peak_bytes": max(
                    (x.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for x in d),
                "bytes_in_use": stats.get("bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
                "engine_init_s": self._bench_init_s}

    def bench_timings(self) -> dict:
        with self._bench_lock:
            return dict(self._bench_timings)

    def bench_clock_gaps(self) -> list:
        """(when, seconds) of every time this process's clock thread woke
        0.1 s late or more: the replica, or the host, stood still."""
        return list(self._bench_clock_gaps)

    def bench_trace_start(self, log_dir: str) -> float:
        from perfbench import trace_reduce
        trace_reduce.start(log_dir)
        return time.monotonic()

    def bench_trace_stop(self) -> float:
        from perfbench import trace_reduce
        t = time.monotonic()
        trace_reduce.stop()
        return t

    def bench_reference(self, cases) -> dict:
        """Teacher-forced check on this replica's own weights: for each
        (prompt, generated) the reference's gap per generated token."""
        from perfbench import spec
        gaps_of = spec.family_of(self._bench_config).teacher_forced_gaps
        pad = max(len(p) + len(g) for p, g in cases)
        pad = -(-pad // 128) * 128
        out = [gaps_of(
            self.engine.params, self._bench_config, p, g, pad_to=pad,
            with_spread=True) for p, g in cases]
        return {"gaps": [g for g, _ in out],
                "logit_std": sum(s for _, s in out) / len(out)}
