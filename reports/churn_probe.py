"""Serving availability under replica churn (bench.py subprocess; the
robustness counterpart of serve_probe.py).

Stands up a real Serve deployment (LLMDeployment over the
continuous-batching engine, multiple replicas), drives seeded Poisson
arrivals of STREAMING requests, and measures the same workload twice:

- **quiet**: no failures — the availability baseline;
- **churn**: rolling replica losses while the load runs — alternating
  graceful preemption notices (ServeReplicaKiller.preempt_one: drain ->
  replace) and hard kills (kill_one(prefer_busy=True): the stream-resume
  path), at least ``min_losses`` of them.

Per stream the probe checks EXACTLY-ONCE token delivery against a local
greedy reference engine (same params seed): a missing position counts as
dropped, a repeated one as duplicated. Reported:

  error_rate            failed streams / total (churn phase)
  dropped_streams       streams that died without resuming
  dropped_tokens / duplicated_tokens   vs the greedy reference
  ttft_p95_ms_quiet / ttft_p95_ms_churn   tail latency cost of churn
  losses = {"preempted": n, "killed": n}

Usage: python churn_probe.py --one '{"n_replicas": 2, "n_requests": 16}'
Prints one line: RESULT {json}

Needs the cluster runtime (Python >= 3.12); bench.py records a skip
reason on older interpreters.
"""

import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _tiny_cfg():
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=512, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)


def _workload(spec, rng):
    n = spec.get("n_requests", 16)
    plo, phi = spec.get("prompt_lens", [4, 24])
    nlo, nhi = spec.get("new_tokens", [24, 48])
    reqs = []
    for _ in range(n):
        p = int(rng.integers(plo, phi + 1))
        reqs.append({
            "prompt": [int(t) for t in rng.integers(1, 100, size=p)],
            "new": int(rng.integers(nlo, nhi + 1)),
        })
    rate = spec.get("arrival_rate_rps", 4.0)
    gaps = rng.exponential(1.0 / rate, size=n)
    arrivals = gaps.cumsum()
    arrivals[0] = 0.0
    return reqs, arrivals


def _reference_tokens(spec, reqs):
    """Greedy expectations from a local engine with the same params
    seed the replicas use — the exactly-once oracle."""
    from ray_tpu.inference import LLMDeployment
    dep = LLMDeployment(_tiny_cfg(), n_slots=spec.get("n_slots", 2),
                        max_len=512, prefill_chunk=8, prefill_budget=16)
    try:
        return [dep.generate(r["prompt"], max_new_tokens=r["new"])
                for r in reqs]
    finally:
        dep.engine.stop()


def _drive(handle, reqs, arrivals, expected):
    """One pass of Poisson-arrival streams; returns per-stream results:
    {"tokens": [...], "ttft_ms": float|None, "error": str|None}."""
    results = [None] * len(reqs)

    def one(i):
        r = reqs[i]
        out, ttft, err = [], None, None
        t0 = time.perf_counter()
        try:
            gen = handle.options(stream=True).remote(
                r["prompt"], max_new_tokens=r["new"])
            for tok in gen:
                if ttft is None:
                    ttft = (time.perf_counter() - t0) * 1e3
                out.append(tok)
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
        results[i] = {"tokens": out, "ttft_ms": ttft, "error": err}

    threads = []
    t_start = time.perf_counter()
    for i, at in enumerate(arrivals):
        delay = at - (time.perf_counter() - t_start)
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=one, args=(i,), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=300)
    dropped_tok = dup_tok = dropped_streams = errors = 0
    for res, exp in zip(results, expected):
        if res is None or res["error"] is not None:
            errors += 1
            dropped_streams += 1
            continue
        got = res["tokens"]
        if got != exp:
            # positional diff against the greedy oracle: a short stream
            # dropped its tail, a long one duplicated, and any in-place
            # mismatch counts against exactly-once delivery too
            if len(got) < len(exp):
                dropped_tok += len(exp) - len(got)
            elif len(got) > len(exp):
                dup_tok += len(got) - len(exp)
            dup_tok += sum(1 for a, b in zip(got, exp) if a != b)
    ttfts = sorted(r["ttft_ms"] for r in results
                   if r and r["ttft_ms"] is not None)
    p95 = ttfts[int(len(ttfts) * 0.95)] if ttfts else None
    return {"errors": errors, "dropped_streams": dropped_streams,
            "dropped_tokens": dropped_tok, "duplicated_tokens": dup_tok,
            "ttft_p95_ms": round(p95, 1) if p95 is not None else None}


def run(spec):
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.inference import LLMDeployment
    from ray_tpu.util.chaos import ServeReplicaKiller

    n_replicas = spec.get("n_replicas", 2)
    ray_tpu.init(num_cpus=max(4, 2 * n_replicas))
    try:
        dep = serve.deployment(LLMDeployment, num_replicas=n_replicas,
                               preempt_grace_s=20.0)
        serve.run(dep.bind(_tiny_cfg(), n_slots=spec.get("n_slots", 2),
                           max_len=512, prefill_chunk=8,
                           prefill_budget=16),
                  name="churn")
        handle = serve.get_app_handle("churn")
        rng = np.random.default_rng(spec.get("seed", 0))
        reqs, arrivals = _workload(spec, rng)
        expected = _reference_tokens(spec, reqs)

        # warm every replica's engine programs (slow first compiles
        # would read as churn-caused TTFT)
        for _ in range(n_replicas + 1):
            list(handle.options(stream=True).remote([1, 2],
                                                    max_new_tokens=2))

        quiet = _drive(handle, reqs, arrivals, expected)

        killer = ServeReplicaKiller("churn", "LLMDeployment",
                                    seed=spec.get("seed", 0))
        stop = threading.Event()
        min_losses = spec.get("min_losses", 3)

        def churn_loop():
            i = 0
            while not stop.is_set():
                if stop.wait(spec.get("loss_interval_s", 3.0)):
                    return
                try:
                    if i % 2 == 0:
                        killer.preempt_one()
                    else:
                        killer.kill_one(prefer_busy=True)
                except Exception:
                    pass
                killer.wait_for_replacement(timeout_s=60,
                                            min_running=n_replicas,
                                            handle=handle)
                i += 1

        churner = threading.Thread(target=churn_loop, daemon=True)
        churner.start()
        churn = _drive(handle, reqs, arrivals, expected)
        extra_rounds = 0
        while (killer.killed + killer.preempted < min_losses
               and extra_rounds < 10):
            # keep the load alive until enough losses landed
            extra = _drive(handle, reqs[:4], arrivals[:4], expected[:4])
            for k in ("errors", "dropped_streams", "dropped_tokens",
                      "duplicated_tokens"):
                churn[k] += extra[k]
            extra_rounds += 1
        stop.set()
        churner.join(timeout=30)

        total = len(reqs)
        return {
            "n_replicas": n_replicas, "n_requests": total,
            "arrival_rate_rps": spec.get("arrival_rate_rps", 4.0),
            "losses": {"preempted": killer.preempted,
                       "killed": killer.killed},
            "error_rate": round(churn["errors"] / max(total, 1), 4),
            "dropped_streams": churn["dropped_streams"],
            "dropped_tokens": churn["dropped_tokens"],
            "duplicated_tokens": churn["duplicated_tokens"],
            "ttft_p95_ms_quiet": quiet["ttft_p95_ms"],
            "ttft_p95_ms_churn": churn["ttft_p95_ms"],
            "vs_quiet_p95": (round(churn["ttft_p95_ms"]
                                   / quiet["ttft_p95_ms"], 3)
                             if quiet["ttft_p95_ms"]
                             and churn["ttft_p95_ms"] else None),
        }
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


def run_multi_model(spec):
    """Multi-model fleet churn (bench `multi_model_churn`, extending
    `serve_availability_under_churn` with ROADMAP item 3's scenario):
    N tiny-model deployments share one cluster, zipf traffic across
    models AND tenants, the coldest model scales to zero and must
    revive through a pre-warmed shell at least once. Reported:

      cold_start_p99_ms      fleet-view revival latency percentile
      revivals               scale-to-zero revivals observed (>= 1)
      tenant_p95_ms          per-tenant client-side p95 split
      serve_tenant_shed_total  requests shed by the admission gate
      errors                 failed streams (expected 0)

    Tenancy runs through the real ingress component (serve/fleet.py
    TenantAdmission — the same object the HTTP proxy runs), driven
    directly so the probe sheds deterministically without an HTTP hop.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.inference import LLMDeployment
    from ray_tpu.serve.fleet import TenantAdmission, TenantQuotaExceeded

    n_models = int(spec.get("n_models", 3))
    n_tenants = int(spec.get("n_tenants", 4))
    n_requests = int(spec.get("n_requests", 24))
    idle_s = float(spec.get("idle_scale_to_zero_s", 2.0))
    rng = np.random.default_rng(spec.get("seed", 0))
    ray_tpu.init(num_cpus=max(4, 2 * n_models))
    try:
        cold_app = f"m{n_models - 1}"
        handles = {}
        for i in range(n_models):
            app = f"m{i}"
            dep = serve.deployment(LLMDeployment, name=f"llm{i}",
                                   num_replicas=1)
            if app == cold_app:
                dep = dep.options(autoscaling_config={
                    "min_replicas": 0, "max_replicas": 1,
                    "target_ongoing_requests": 2.0,
                    "look_back_period_s": 1.0, "downscale_delay_s": 0.5,
                    "idle_scale_to_zero_s": idle_s})
            serve.run(dep.bind(_tiny_cfg(), n_slots=spec.get("n_slots", 2),
                               max_len=512, prefill_chunk=8,
                               prefill_budget=16), name=app)
            handles[app] = serve.get_app_handle(app)
        for h in handles.values():   # warm compiles out of the timings
            list(h.options(stream=True).remote([1, 2], max_new_tokens=2))

        # idle the cold model past its window -> scale-to-zero
        deadline = time.time() + 90
        scaled = False
        while time.time() < deadline:
            st = serve.status()[cold_app][f"llm{n_models - 1}"]
            if st["running"] == 0 and st["target"] == 0:
                scaled = True
                break
            time.sleep(0.5)

        # zipf traffic over models (m0 hottest) and tenants (t0 hottest)
        # through the real admission gate; the hot tenant's quota forces
        # shedding under its own burst, never the quiet tenants'
        adm = TenantAdmission(default_quota=int(spec.get("tenant_quota", 2)),
                              queue_max=int(spec.get("tenant_queue_max", 2)))
        zm = (1.0 / np.arange(1, n_models + 1)) ** 1.1
        zt = (1.0 / np.arange(1, n_tenants + 1)) ** 1.1
        picks_m = rng.choice(n_models, size=n_requests, p=zm / zm.sum())
        picks_t = rng.choice(n_tenants, size=n_requests, p=zt / zt.sum())
        picks_m[-1] = n_models - 1      # the cold model IS exercised
        gaps = rng.exponential(1.0 / spec.get("arrival_rate_rps", 6.0),
                               size=n_requests)
        lat = {f"t{i}": [] for i in range(n_tenants)}
        errors = []

        def one(mi, ti):
            tenant = f"t{ti}"
            t0 = time.perf_counter()
            try:
                lease = adm.acquire(tenant, timeout_s=30)
            except TenantQuotaExceeded:
                return          # shed: counted by the admission gate
            try:
                h = handles[f"m{mi}"].options(stream=True, tenant=tenant)
                out = [t for t in h.remote(
                    [1 + int(mi), 2, 3], max_new_tokens=8)]
                if not out:
                    errors.append("empty")
                lat[tenant].append((time.perf_counter() - t0) * 1e3)
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")
            finally:
                lease.release()

        threads = []
        for (mi, ti, gap) in zip(picks_m, picks_t, gaps):
            time.sleep(float(gap))
            th = threading.Thread(target=one, args=(int(mi), int(ti)),
                                  daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=300)

        fleet = serve.fleet_status()
        cold_key = f"{cold_app}/llm{n_models - 1}"
        cold_stats = (fleet.get("fleet") or {}).get(
            "cold_starts", {}).get(cold_key, {})
        tenant_p95 = {}
        for t, xs in lat.items():
            if xs:
                xs = sorted(xs)
                tenant_p95[t] = round(xs[int(len(xs) * 0.95)
                                         if len(xs) > 1 else 0], 1)
        shed = (adm.stats() or {}).get("shed_total", {})
        return {
            "n_models": n_models, "n_tenants": n_tenants,
            "n_requests": n_requests,
            "scaled_to_zero": scaled,
            "revivals": (fleet.get("fleet") or {}).get("revivals_total", 0),
            "cold_start_p99_ms": cold_stats.get("p99_ms"),
            "cold_start_count": cold_stats.get("count", 0),
            "tenant_p95_ms": tenant_p95,
            "serve_tenant_shed_total": {t: int(n)
                                        for t, n in shed.items()},
            "errors": len(errors), "error_detail": errors[:3],
        }
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


if __name__ == "__main__":
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()   # before the first compile; children inherit
    spec = json.loads(sys.argv[sys.argv.index("--one") + 1])
    fn = run_multi_model if spec.get("mode") == "multi_model" else run
    print("RESULT " + json.dumps(fn(spec)), flush=True)
