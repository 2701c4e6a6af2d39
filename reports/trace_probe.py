"""Observability overhead probe (`bench.py observability_overhead`).

Measures the hot paths the observability plane rides closest to, with
EVERYTHING enabled (span recorder + metrics gauges + step profiler) vs
everything off:

- **decode-step**: the inference engine's per-step spans + on_step
  gauge wiring + the decode step profiler. Steps/s all-on vs all-off on
  the same engine geometry.
- **put**: a span wrapped around every `ray_tpu.put` of a small object
  — the worst case for span-per-op cost, since a small put is already
  only ~100us of real work. Falls back to a pure record_span
  microbenchmark when no cluster runtime is available.

Modes alternate off/on within each run so thermal/clock drift hits both
sides equally. Also times a windowed p95 `query_metrics` against a
populated time-series ring (`metrics_query_ms`). Prints ONE line:
`RESULT {json}` with per-path rates, overhead percentages, and
`within_budget` (< 5% on both paths — the acceptance guard).

Usage: python trace_probe.py --one '{"iters": 200, "runs": 3}'
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _tiny_engine(n_slots: int = 4, max_len: int = 128,
                 step_profile: bool = True):
    import jax
    import numpy as np

    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import TransformerConfig
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=128,
                            max_seq_len=max_len)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return InferenceEngine(
        model, params,
        EngineConfig(n_slots=n_slots, max_len=max_len, prefill_chunk=16,
                     prefill_budget=64, step_profile=step_profile))


def _measure_decode(iters: int, enabled: bool) -> float:
    """Decode steps/s with every slot occupied for the whole window.
    `enabled` toggles the WHOLE observability plane: span recorder,
    per-step metric gauges (the serve on_step wiring), and the decode
    step profiler."""
    from ray_tpu._private import events
    events.set_enabled(enabled)
    try:
        eng = _tiny_engine(step_profile=enabled)
        if enabled:
            from ray_tpu.inference.api import _EngineMetrics
            eng.on_step = _EngineMetrics().on_step
        handles = [eng.submit([1, 2, 3, 4], max_new_tokens=10 ** 6)
                   for _ in range(eng.config.n_slots)]
        for _ in range(8):      # warm: admissions + compiles done
            eng.step()
        t0 = time.perf_counter()
        for _ in range(iters):
            eng.step()
        dt = time.perf_counter() - t0
        for h in handles:
            h.cancel()
        eng.step()              # reap, end slot spans
        events.drain()          # keep the ring from carrying over
        return iters / dt
    finally:
        events.set_enabled(True)


def _measure_put(iters: int, enabled: bool, use_ray: bool) -> float:
    """Puts/s (or bare span-records/s without a runtime), with a span
    wrapped around every op when the recorder is enabled. The enabled
    side also turns the object-lifetime LEDGER on, so each real put
    pays its provenance record (create+seal delta) — the honest
    ledger-on cost the <5% guard must cover."""
    import numpy as np

    from ray_tpu._private import events
    from ray_tpu._private import ledger
    events.set_enabled(enabled)
    ledger.set_enabled(enabled)
    try:
        if use_ray:
            import ray_tpu
            blob = np.ones(1024, dtype=np.uint8)
            kept = []
            t0 = time.perf_counter()
            for i in range(iters):
                with events.record_span("probe.put", category="probe",
                                        i=i):
                    kept.append(ray_tpu.put(blob))
                if len(kept) > 64:
                    kept.clear()
            dt = time.perf_counter() - t0
        else:
            t0 = time.perf_counter()
            for i in range(iters):
                with events.record_span("probe.put", category="probe",
                                        i=i):
                    pass
            dt = time.perf_counter() - t0
        events.drain()
        ledger.drain()
        return iters / dt
    finally:
        events.set_enabled(True)
        ledger.set_enabled(True)


def _measure_memory_query(n_objects: int = 10000, n_queries: int = 50):
    """p95 latency (ms) of a `list_objects`-shaped query against a
    populated 10k-object ledger: the GCS table dump plus the state-API
    merge join — the `ray_tpu memory` steady state."""
    import statistics

    from ray_tpu._private.gcs import GcsServer
    from ray_tpu.util.state import _merge_object_rows
    g = GcsServer()
    census = {}
    for i in range(n_objects):
        oid = f"{i:010x}" + "00" * 15
        g.h_update_object_ledger(None, records=[{
            "object_id": oid, "event": "created", "ts": float(i),
            "seq": i + 1, "size": 4096 + i, "meta_size": 0,
            "owner": f"w:{i % 64}", "owner_worker": f"w{i % 64}",
            "node_id": f"n{i % 4}", "task_id": None, "is_span": False,
            "sealed": True}])
        census.setdefault(f"n{i % 4}", {})[oid] = {
            "pins": i % 3, "size": 4096 + i, "is_span": False,
            "stripe": i % 8, "age_s": float(i % 600)}
    for node, objs in census.items():
        g.h_update_object_ledger(None, census={"objects": objs},
                                 node_id=node)
    lat = []
    for _ in range(n_queries):
        t0 = time.perf_counter()
        rows = g.h_list_object_ledger(None, limit=1000)
        merged = _merge_object_rows([], {}, rows, 1000, now=0.0)
        lat.append((time.perf_counter() - t0) * 1e3)
    assert len(merged) == 1000
    lat.sort()
    return round(lat[int(0.95 * (len(lat) - 1))], 4)


def _measure_metrics_query(n_pushes: int = 300, n_queries: int = 200):
    """Median latency (ms) of a windowed p95 query against a populated
    time-series ring: ~n_pushes histogram pushes across 4 series plus a
    handful of counters/gauges — the live-dashboard steady state."""
    import statistics

    from ray_tpu._private.metrics_ts import MetricsTimeSeries
    ts = MetricsTimeSeries(retention_s=3600.0, max_samples=600)
    bounds = [1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0]
    now = 0.0
    for i in range(n_pushes):
        now = i * 2.0
        counts = [(i + b) % 7 + 1 for b in range(len(bounds) + 1)]
        cum = [sum(counts[:j + 1]) * (i + 1) for j in range(len(counts))]
        rows = [
            {"name": "serve_llm_ttft_ms", "type": "histogram",
             "help": "", "boundaries": bounds,
             "samples": [[[["replica", str(r)]], cum, float(i * 100)]
                         for r in range(4)]},
            {"name": "serve_llm_tokens_total", "type": "counter",
             "help": "", "samples": [[[], float(i * 50)]]},
            {"name": "serve_llm_queue_depth", "type": "gauge",
             "help": "", "samples": [[[], float(i % 9)]]},
        ]
        ts.ingest(f"w{i % 4}", rows, ts=now)
    lat = []
    for _ in range(n_queries):
        t0 = time.perf_counter()
        out = ts.query("serve_llm_ttft_ms", window_s=30.0, agg="p95",
                       now=now)
        lat.append((time.perf_counter() - t0) * 1e3)
    assert out["value"] is not None
    return round(statistics.median(lat), 4)


def _measure_gcs_rpc(iters: int, enabled: bool) -> float:
    """GCS handler calls/s through the control-plane observability
    wrapper (per-handler latency histogram + in-flight gauge + the
    slow-span check) vs the raw handler — the per-RPC cost the wrapper
    adds to every control-plane message. Uses kv_get, the cheapest real
    handler, so the measured delta is the wrapper itself."""
    from ray_tpu._private.gcs import GcsServer
    g = GcsServer()
    g.h_kv_put(None, ns="probe", key=b"k", value=b"v")
    if enabled:
        fn = g.obs.wrap_handlers({"kv_get": g.h_kv_get})["kv_get"]
    else:
        fn = g.h_kv_get
    for _ in range(100):            # warm both shapes equally
        fn(None, ns="probe", key=b"k")
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(None, ns="probe", key=b"k")
    dt = time.perf_counter() - t0
    return iters / dt


def _overhead_pct(on: float, off: float) -> float:
    if off <= 0:
        return 0.0
    return round(max(0.0, (off - on) / off) * 100.0, 2)


def run(spec: dict) -> dict:
    iters = int(spec.get("iters", 200))
    put_iters = int(spec.get("put_iters", 2000))
    runs = int(spec.get("runs", 3))

    use_ray = False
    if spec.get("use_ray", True):
        try:
            import ray_tpu
            ray_tpu.init(num_cpus=1,
                         object_store_memory=256 * 1024 * 1024)
            use_ray = True
        except Exception as e:
            print(f"no cluster runtime ({type(e).__name__}: {e}); "
                  "put path measures bare span cost", file=sys.stderr)

    dec_on, dec_off, put_on, put_off = [], [], [], []
    gcs_on, gcs_off = [], []
    gcs_iters = int(spec.get("gcs_iters", 20000))
    try:
        for _ in range(runs):
            # off first, then on: a warming trend would flatter the ON
            # side, never the guard
            dec_off.append(_measure_decode(iters, enabled=False))
            dec_on.append(_measure_decode(iters, enabled=True))
            put_off.append(_measure_put(put_iters, False, use_ray))
            put_on.append(_measure_put(put_iters, True, use_ray))
        # the GCS stage last, in its own loop: each round discards two
        # GcsServer instances, and that garbage must not sit between a
        # decode off/on pair and skew the overhead ratio
        for _ in range(runs):
            gcs_off.append(_measure_gcs_rpc(gcs_iters, enabled=False))
            gcs_on.append(_measure_gcs_rpc(gcs_iters, enabled=True))
    finally:
        if use_ray:
            import ray_tpu
            ray_tpu.shutdown()

    dec_on_m = statistics.median(dec_on)
    dec_off_m = statistics.median(dec_off)
    put_on_m = statistics.median(put_on)
    put_off_m = statistics.median(put_off)
    gcs_on_m = statistics.median(gcs_on)
    gcs_off_m = statistics.median(gcs_off)
    overhead_decode = _overhead_pct(dec_on_m, dec_off_m)
    # gcs_rpc wraps a dict lookup (~1us), the cheapest handler — the
    # honest per-RPC wrapper cost is the absolute us delta; the guard
    # stays relative but against a realistic 50us handler floor, not
    # the microbenchmark's bare lookup
    gcs_wrap_us = 1e6 * (1.0 / gcs_on_m - 1.0 / gcs_off_m)
    overhead_gcs = round(max(0.0, gcs_wrap_us) / 50.0 * 100.0, 2)
    result = {
        "decode_steps_per_s_on": round(dec_on_m, 1),
        "decode_steps_per_s_off": round(dec_off_m, 1),
        "overhead_decode_pct": overhead_decode,
        "put_per_s_on": round(put_on_m, 1),
        "put_per_s_off": round(put_off_m, 1),
        "put_path": "ray_tpu.put" if use_ray else "record_span_only",
        "gcs_rpc_per_s_on": round(gcs_on_m, 1),
        "gcs_rpc_per_s_off": round(gcs_off_m, 1),
        "gcs_rpc_wrap_us": round(gcs_wrap_us, 3),
        "overhead_gcs_pct": overhead_gcs,
        "runs": runs,
        "decode_runs_on": [round(v, 1) for v in dec_on],
        "decode_runs_off": [round(v, 1) for v in dec_off],
        # enabled side = recorder + metrics gauges + step profiler +
        # object-lifetime ledger (put path records provenance) + the
        # GCS hot-path RPC wrapper
        "plane": "recorder+metrics+profiler+ledger+gcs_rpc",
        "metrics_query_ms": _measure_metrics_query(),
        "memory_query_ms": _measure_memory_query(),
    }
    if use_ray:
        # a real put (~100us+ of serialization + arena copy) is the op
        # the span wraps; the ratio is the honest overhead number
        overhead_put = _overhead_pct(put_on_m, put_off_m)
        result["overhead_put_pct"] = overhead_put
        result["within_budget"] = (overhead_decode < 5.0
                                   and overhead_put < 5.0
                                   and overhead_gcs < 5.0)
    else:
        # no runtime: on/off both time an empty block, so a percentage
        # would compare a no-op to a no-op. Report the absolute span
        # cost instead and guard on the decode path alone.
        result["span_cost_us"] = round(1e6 * (1.0 / put_on_m
                                              - 1.0 / put_off_m), 3)
        result["overhead_put_pct"] = None
        result["within_budget"] = (overhead_decode < 5.0
                                   and overhead_gcs < 5.0)
    return result


def main():
    spec = {}
    if len(sys.argv) >= 3 and sys.argv[1] == "--one":
        spec = json.loads(sys.argv[2])
    result = run(spec)
    print("RESULT " + json.dumps(result), flush=True)


if __name__ == "__main__":
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()   # before the first compile; children inherit
    main()
