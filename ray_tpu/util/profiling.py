"""Per-step time/FLOP attribution: where does a training step go?

ROADMAP item 5 has `train_step_mfu` stuck at 0.564 with zero in-runtime
visibility into where step time is spent (a figure from before PR 21;
perfbench/ measures `mfu` on the chip now, once a run, not live). The
step-level attribution that both the Gemma-on-TPU serving study (arXiv
2605.25645) and the MPMD pipeline work (arXiv 2412.14374) lean on before
optimizing is exactly: FLOPs from the compiled program
(``compiled.cost_analysis()``) divided over measured wall phases.

``StepProfiler`` combines three marks per step with a FLOP/byte cost:

- **host gap**  — time between the previous step's end and this step's
  begin (logging, checkpointing, scheduler bookkeeping);
- **data wait** — begin → ``data_ready()`` (input pipeline);
- **compute**   — ``data_ready()`` → end (dispatch + device, the caller
  blocks on the step's output before ending).

and emits, per step (through the existing metrics registry, so the
values land in /metrics AND the GCS time-series plane):

  runtime_<name>_mfu             gauge   FLOPs / (wall * peak)
  runtime_<name>_mfu_compute     gauge   FLOPs / (compute * peak) — the
                                         hardware-bound ceiling
  runtime_<name>_phase_ms        gauge   tags: phase=compute|host_gap|
                                         data_wait
  runtime_<name>_roofline_bound  gauge   min(1, intensity / machine
                                         balance): the MFU an ideal
                                         schedule of this program could
                                         reach on this chip
  runtime_<name>_tokens_per_s    gauge   when step_begin(tokens=) given

plus (``emit_span=True``) a flight-recorder span per step carrying the
same attribution, so the stuck-MFU question is readable off the
timeline instead of requiring the offline harness.

Cost sources, in order of preference: ``wrap_jit`` (AOT lower+compile
once per input shape — cost_analysis comes free and the compiled
executable is reused, no double compile), ``observe_compiled`` (caller
already has an AOT executable), ``set_cost`` (analytic formulas).

The inference engine is not among its users: a serving step is told by
phase on the profiler's own clock (inference/engine.py ``_StepPhases``),
and what a step must compute or move is the model family's to say
(perfbench/families/), not a dense model's arithmetic.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, Optional

logger = logging.getLogger(__name__)

# Peak rates per chip, one table keyed by ``device_kind`` (lower-cased
# substring match). Source: Google Cloud TPU documentation, the "System
# architecture" page of each generation (v4, v5e, v5p, v6e): peak bf16
# compute per chip and HBM bandwidth per chip — for the v5e ("TPU v5
# lite"): 197 TFLOP/s bf16, 819 GB/s. A kind that is not in the table is
# an error, never a default. The CPU row is a NOMINAL figure — CPU MFU is
# a relative utilization signal for tests/dev boxes, not a hardware
# claim. RAY_TPU_PEAK_FLOPS / RAY_TPU_PEAK_BYTES_PER_S override the table.
_PEAK_FLOPS_BY_KIND = {
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,
    "tpu v5e": 197e12,
    "tpu v5p": 459e12,
    "tpu v6 lite": 918e12,
    "tpu v6e": 918e12,
    "cpu": 1e11,
}
# HBM bandwidth (bytes/s) per chip for the roofline machine balance.
_PEAK_BYTES_BY_KIND = {
    "tpu v4": 1.2e12,
    "tpu v5 lite": 8.19e11,
    "tpu v5e": 8.19e11,
    "tpu v5p": 2.765e12,
    "tpu v6 lite": 1.64e12,
    "tpu v6e": 1.64e12,
    "cpu": 5e10,
}


def _device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind.lower()


def _lookup(table: Dict[str, float], kind: str) -> float:
    for key, v in table.items():
        if key in kind:
            return v
    raise KeyError(
        f"device kind {kind!r} is not in the peak table "
        f"(ray_tpu/util/profiling.py): add its published rates there")


def detect_peak_flops() -> float:
    env = os.environ.get("RAY_TPU_PEAK_FLOPS")
    if env:
        return float(env)
    return _lookup(_PEAK_FLOPS_BY_KIND, _device_kind())


def detect_peak_bytes_per_s() -> float:
    env = os.environ.get("RAY_TPU_PEAK_BYTES_PER_S")
    if env:
        return float(env)
    return _lookup(_PEAK_BYTES_BY_KIND, _device_kind())


def device_report() -> Dict[str, Any]:
    """What this process's JAX runs on, as JAX reports it, with the
    table's peaks for that kind (raises for a kind the table lacks)."""
    import jax
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
            "table_peak_flops_per_s": detect_peak_flops(),
            "table_peak_bytes_per_s": detect_peak_bytes_per_s()}


def cost_of_compiled(compiled) -> Dict[str, float]:
    """FLOPs / bytes-accessed from an AOT ``Compiled``'s cost analysis
    (jax returns one dict per partition; sum them)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, dict):
        ca = [ca]
    flops = sum(float(d.get("flops", 0.0) or 0.0) for d in ca or [])
    nbytes = sum(float(d.get("bytes accessed", 0.0) or 0.0)
                 for d in ca or [])
    return {"flops": flops, "bytes_accessed": nbytes}


def _shape_key(tree) -> tuple:
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef,
            tuple((getattr(x, "shape", ()), str(getattr(x, "dtype", type(x))))
                  for x in leaves))


class _StepScope:
    """Context manager for one profiled step — see StepProfiler.step()."""

    __slots__ = ("_prof", "_tokens", "_t0", "_t_data")

    def __init__(self, prof: "StepProfiler", tokens: Optional[int]):
        self._prof = prof
        self._tokens = tokens
        self._t0 = time.perf_counter()
        self._t_data: Optional[float] = None

    def data_ready(self):
        """Input pipeline done; compute starts now."""
        self._t_data = time.perf_counter()

    def block(self, out) -> None:
        """Block on the step's output so the compute phase includes
        device time, not just dispatch."""
        try:
            import jax
            jax.block_until_ready(out)
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        end = time.perf_counter()
        data_t = self._t_data or self._t0
        self._prof.observe(
            compute_s=end - data_t, data_s=data_t - self._t0,
            begin_t=self._t0, end_t=end, tokens=self._tokens,
            failed=exc_type is not None)
        return False


class StepProfiler:
    """Thread-compatible (one step in flight per profiler instance);
    creating one registers its gauges, which starts the metrics pusher
    lazily like any other metric."""

    def __init__(self, name: str = "train_step",
                 peak_flops: Optional[float] = None,
                 peak_bytes_per_s: Optional[float] = None,
                 emit_span: bool = True, emit_every: int = 1,
                 category: str = "profile"):
        from ray_tpu.util.metrics import Gauge
        self.name = name
        self.category = category
        self.emit_span = emit_span
        self.emit_every = max(1, int(emit_every))
        self.peak_flops = peak_flops or detect_peak_flops()
        self.peak_bytes_per_s = peak_bytes_per_s or detect_peak_bytes_per_s()
        self.flops: float = 0.0
        self.bytes_accessed: float = 0.0
        self.steps = 0
        self.last: Dict[str, Any] = {}
        self._prev_end: Optional[float] = None
        self._lock = threading.Lock()
        self._g_mfu = Gauge(f"runtime_{name}_mfu",
                            f"model FLOPs utilization of the {name} loop "
                            "(wall clock incl. host gap + data wait)")
        self._g_mfu_c = Gauge(f"runtime_{name}_mfu_compute",
                              f"{name} MFU over the compute phase only "
                              "(the hardware-bound ceiling)")
        self._g_phase = Gauge(f"runtime_{name}_phase_ms",
                              f"per-step {name} phase attribution (ms)",
                              tag_keys=("phase",))
        self._g_roof = Gauge(f"runtime_{name}_roofline_bound",
                             f"roofline MFU bound of the {name} program "
                             "(arithmetic intensity / machine balance)")
        self._g_tps = Gauge(f"runtime_{name}_tokens_per_s",
                            f"{name} tokens per wall second")

    # --------------------------------------------------------------- cost
    def set_cost(self, flops: float, bytes_accessed: float = 0.0):
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        return self

    def observe_compiled(self, compiled) -> bool:
        """Read FLOPs/bytes off an AOT-compiled executable. Returns
        False (cost left untouched) when the backend exposes none."""
        try:
            cost = cost_of_compiled(compiled)
        except Exception:
            return False
        if cost["flops"] <= 0 and cost["bytes_accessed"] <= 0:
            return False
        self.set_cost(cost["flops"], cost["bytes_accessed"])
        return True

    def wrap_jit(self, jit_fn):
        """Wrap a ``jax.jit`` function so each input shape is AOT
        lowered+compiled exactly once, its cost analysis feeds this
        profiler, and subsequent calls reuse the compiled executable.
        Any failure (backend without AOT, sharding-strict executables
        rejecting an input) falls back to the plain jitted call for that
        shape — the profiler then just has no FLOP count."""
        cache: Dict[tuple, tuple] = {}

        def call(*args):
            try:
                key = _shape_key(args)
            except Exception:
                return jit_fn(*args)
            entry = cache.get(key)
            if entry is None:
                fn, cost = jit_fn, None
                try:
                    compiled = jit_fn.lower(*args).compile()
                    cost = cost_of_compiled(compiled)
                    fn = compiled
                except Exception as e:
                    logger.debug("AOT cost analysis unavailable for %s: %s",
                                 self.name, e)
                entry = cache[key] = (fn, cost)
            fn, cost = entry
            if cost is not None:
                self.set_cost(cost["flops"], cost["bytes_accessed"])
            try:
                return fn(*args)
            except Exception:
                if fn is jit_fn:
                    raise
                # a strict AOT executable rejected this input (e.g. an
                # uncommitted sharding): pin the fallback for this shape
                cache[key] = (jit_fn, cost)
                return jit_fn(*args)

        return call

    # -------------------------------------------------------------- steps
    def step(self, tokens: Optional[int] = None) -> _StepScope:
        """``with prof.step(tokens=B*L) as s: batch=...; s.data_ready();
        out = step_fn(batch); s.block(out)``"""
        return _StepScope(self, tokens)

    def observe(self, compute_s: float, data_s: float = 0.0,
                begin_t: Optional[float] = None,
                end_t: Optional[float] = None,
                tokens: Optional[int] = None,
                flops: Optional[float] = None,
                bytes_accessed: Optional[float] = None,
                failed: bool = False) -> Dict[str, Any]:
        """Low-level entry (the engine calls this directly with its own
        phase timings). Returns the attribution dict for this step."""
        now = time.perf_counter()
        end_t = now if end_t is None else end_t
        begin_t = (end_t - compute_s - data_s) if begin_t is None \
            else begin_t
        with self._lock:
            gap_s = max(0.0, begin_t - self._prev_end) \
                if self._prev_end is not None else 0.0
            self._prev_end = end_t
            self.steps += 1
            step_n = self.steps
        if flops is not None:
            self.flops = float(flops)
        if bytes_accessed is not None:
            self.bytes_accessed = float(bytes_accessed)
        compute_s = max(0.0, compute_s)
        data_s = max(0.0, data_s)
        wall_s = compute_s + data_s + gap_s
        rec: Dict[str, Any] = {
            "step": step_n,
            "compute_ms": round(compute_s * 1e3, 4),
            "data_wait_ms": round(data_s * 1e3, 4),
            "host_gap_ms": round(gap_s * 1e3, 4),
            "wall_ms": round(wall_s * 1e3, 4),
        }
        if self.flops > 0 and wall_s > 0:
            rec["mfu"] = round(self.flops / wall_s / self.peak_flops, 6)
            if compute_s > 0:
                rec["mfu_compute"] = round(
                    self.flops / compute_s / self.peak_flops, 6)
        if self.flops > 0 and self.bytes_accessed > 0:
            intensity = self.flops / self.bytes_accessed
            balance = self.peak_flops / self.peak_bytes_per_s
            rec["roofline_bound"] = round(min(1.0, intensity / balance), 6)
        if tokens is not None and wall_s > 0:
            rec["tokens_per_s"] = round(tokens / wall_s, 2)
        if failed:
            rec["failed"] = True
        self.last = rec
        if step_n % self.emit_every == 0:
            self._emit(rec, begin_t, end_t)
        return rec

    def _emit(self, rec: Dict[str, Any], begin_t: float, end_t: float):
        try:
            self._g_phase.set(rec["compute_ms"], tags={"phase": "compute"})
            self._g_phase.set(rec["data_wait_ms"],
                              tags={"phase": "data_wait"})
            self._g_phase.set(rec["host_gap_ms"],
                              tags={"phase": "host_gap"})
            if "mfu" in rec:
                self._g_mfu.set(rec["mfu"])
            if "mfu_compute" in rec:
                self._g_mfu_c.set(rec["mfu_compute"])
            if "roofline_bound" in rec:
                self._g_roof.set(rec["roofline_bound"])
            if "tokens_per_s" in rec:
                self._g_tps.set(rec["tokens_per_s"])
        except Exception:
            pass
        if self.emit_span:
            from ray_tpu._private import events
            # wall-clock reconstruction: perf_counter deltas applied to
            # time.time() so the span lines up with the rest of the
            # timeline
            t_end = time.time() - (time.perf_counter() - end_t)
            t_begin = t_end - (end_t - begin_t)
            events.record_complete(
                f"{self.name}.step", t_begin, t_end,
                category=self.category,
                **{k: v for k, v in rec.items() if k != "step"})
