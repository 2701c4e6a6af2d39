"""Cluster serving-edge probe (`serve_million_sessions`): hermetic, on a
virtual clock; `tests/test_serve_edge.py` runs it scaled down.

Three segments, one RESULT entry (ROADMAP item 2):

1. **edge** — O(100k) synthetic zipf-tenant sessions through >= 2 REAL
   proxy admission stacks (the exact objects serve/proxy.py wires per
   ingress: ``TenantAdmission`` + ``QuotaLeaseClient`` with the
   Retry-After deficit hint) against one real ``GcsServer`` lease table.
   Arrivals run on a virtual clock so 100k sessions take seconds while
   the token-bucket arithmetic sees honest inter-arrival gaps; the
   reported ``p99_ttft_ms`` is the measured wall-clock latency of the
   admission + dispatch edge itself (model compute is segment 2's job).
   Mid-run a ``QuotaLeaseRevoker`` revokes one proxy's lease
   (rolling, chaos satellite): the victim must degrade to its
   conservative local share until re-lease, and the entry asserts ZERO
   over-admission — for every rated tenant, cluster-wide admissions
   stay under rate * duration + burst throughout.
2. **fabric** — decode→decode KV hand-off measured on real engines: N
   sessions over K shared prefixes split across two decode replicas
   with the KV fabric on vs the same split with the fabric off (the
   prefill-funnel baseline shape: every replica pays its own prefill).
   ``cluster_prefix_hit_rate`` must improve, greedy output stays
   bit-identical to a colocated oracle, decode_compile_count stays 1.
3. **batched_export** — K=8 concurrent misses on ONE fingerprint
   produce exactly 1 export (single-flight) with K-1 coalesced
   followers, and the broadcast-tree plan over the waiters' nodes
   (data_plane.binomial_split — the same planner store.broadcast
   executes) relays in <= log2(K)+1 hops.

Usage: python edge_probe.py --one '{"n_sessions": 100000, "proxies": 2}'
Prints one line: RESULT {json}
"""

import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


# --------------------------------------------------------------- edge
def _gcs():
    from ray_tpu._private.gcs import GcsServer
    g = GcsServer.__new__(GcsServer)
    g.tenant_quotas = {}
    g.quota_leases = {}
    g.quota_lease_epoch = 1
    g.tenant_burn = {}
    return g


def _locked_call(g):
    """In-process stand-in for the GCS RPC loop: handlers there run
    serialized on one thread, so the shim serializes too."""
    lock = threading.Lock()

    def call(method, **kw):
        with lock:
            return getattr(g, "h_" + method)(None, **kw)
    return call


class _EdgeProxy:
    """One ingress proxy's admission stack — the same objects
    serve/proxy.py builds (TenantAdmission + QuotaLeaseClient, deficit
    retry hint wired), minus the aiohttp shell."""

    def __init__(self, pid, call, clock):
        from ray_tpu.serve.fleet import QuotaLeaseClient, TenantAdmission
        self.pid = pid
        self.adm = TenantAdmission()
        self.lease = QuotaLeaseClient(pid, call, clock=clock)
        self.adm.retry_hint = self.lease.retry_hint
        assert self.lease.acquire()
        self.admitted = 0
        self.shed = 0
        self.lat_ms = []

    def serve(self, tenant, now):
        """One session: leased-rate gate, then concurrency gate, then a
        zero-cost dispatch (the stub deployment). Returns True when the
        session was admitted."""
        from ray_tpu.serve.fleet import TenantQuotaExceeded
        t0 = time.perf_counter()
        wait = self.lease.admit(tenant, now)
        if wait is not None:
            self.shed += 1
            return False
        try:
            lease = self.adm.acquire(tenant)
        except TenantQuotaExceeded:
            self.shed += 1
            return False
        lease.release()
        self.admitted += 1
        self.lat_ms.append((time.perf_counter() - t0) * 1000.0)
        return True


def _p(vals, q):
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(len(vals) * q))] if vals else 0.0


def _run_edge(spec, rng):
    from ray_tpu._private.config import cfg as rt_cfg
    from ray_tpu.util.chaos import QuotaLeaseRevoker

    n = int(spec.get("n_sessions", 100_000))
    n_prox = max(2, int(spec.get("proxies", 2)))
    n_ten = int(spec.get("n_tenants", 8))
    cluster_rate = float(spec.get("cluster_rate_rps", 2000.0))
    offered = float(spec.get("offered_rate_rps", 2.0 * cluster_rate))
    hot_weight = float(spec.get("hot_weight", 2.0))

    g = _gcs()
    call = _locked_call(g)
    # weighted cluster rates: tenant 0 is "hot" (zipf head AND double
    # weight); everyone else weight 1. burst = 1s of the tenant's rate.
    weights = [hot_weight] + [1.0] * (n_ten - 1)
    wsum = sum(weights)
    for t in range(n_ten):
        r = cluster_rate * weights[t] / wsum
        g.h_set_tenant_quota(None, f"t{t}", rate=r, burst=max(1.0, r),
                             weight=weights[t])

    clk = {"t": 1000.0}
    proxies = [_EdgeProxy(f"edge-p{i}", call, lambda: clk["t"])
               for i in range(n_prox)]
    for p in proxies:           # everyone adopts the n-proxy split
        p.lease.maybe_renew(clk["t"] + 1e-6)

    # zipf tenant draw (s=1.2), vectorized up front
    import numpy as np
    zw = (1.0 / np.arange(1, n_ten + 1)) ** 1.2
    tenant_ix = rng.choice(n_ten, size=n, p=zw / zw.sum())
    arrivals = np.arange(n) / offered + clk["t"]

    revoker = QuotaLeaseRevoker(call, seed=int(spec.get("seed", 0)))
    revoke_at = int(n * 0.4)
    degraded_at = None
    restored_at = None
    admitted_by_tenant = [0] * n_ten
    t_wall0 = time.perf_counter()
    for i in range(n):
        now = float(arrivals[i])
        clk["t"] = now
        if i == revoke_at:
            revoker.revoke(proxies[0].pid)   # rolling preemption chaos
        p = proxies[i % n_prox]
        if p.serve(f"t{tenant_ix[i]}", now):
            admitted_by_tenant[tenant_ix[i]] += 1
        if i > revoke_at:
            if degraded_at is None and proxies[0].lease.revoked:
                degraded_at = i              # victim learned; degraded
            elif (degraded_at is not None and restored_at is None
                    and not proxies[0].lease.revoked):
                restored_at = i              # re-leased; full share back
    wall_s = time.perf_counter() - t_wall0
    duration = float(arrivals[-1] - arrivals[0]) if n > 1 else 1.0

    # zero over-admission: the hard bound every rated tenant must obey
    # cluster-wide REGARDLESS of the revocation window (the escrow
    # makes the degraded window strictly more conservative)
    over = {}
    for t in range(n_ten):
        rate = cluster_rate * weights[t] / wsum
        bound = rate * duration + max(1.0, rate) * n_prox
        over[f"t{t}"] = max(0, admitted_by_tenant[t] - int(bound + 1))
    admitted = sum(p.admitted for p in proxies)
    shed = sum(p.shed for p in proxies)
    lat = [v for p in proxies for v in p.lat_ms]
    hot_share = admitted_by_tenant[0] / admitted if admitted else 0.0
    hot_weight_share = hot_weight / wsum
    burn = g.h_quota_lease_status(None)["tenant_burn"]
    return {
        "sessions": n, "proxies": n_prox, "tenants": n_ten,
        "offered_rate_rps": offered, "cluster_rate_rps": cluster_rate,
        "duration_s": round(duration, 1),
        "wall_s": round(wall_s, 2),
        "sessions_per_s_wall": round(n / wall_s, 0) if wall_s else None,
        "admitted": admitted, "shed": shed,
        "p50_ttft_ms": round(_p(lat, 0.50), 4),
        "p99_ttft_ms": round(_p(lat, 0.99), 4),
        "hot_tenant_share": round(hot_share, 4),
        "hot_tenant_weight_share": round(hot_weight_share, 4),
        "fairness_ok": hot_share <= hot_weight_share + 0.10,
        "over_admission": over,
        "over_admission_total": sum(over.values()),
        "revoked_proxy": proxies[0].pid,
        "degraded_after_sessions": (degraded_at - revoke_at
                                    if degraded_at else None),
        "restored_after_sessions": (restored_at - revoke_at
                                    if restored_at else None),
        "gcs_tenant_burn_total": sum(burn.values()),
        "per_proxy": {p.pid: {"admitted": p.admitted, "shed": p.shed,
                              "p99_ttft_ms": round(_p(p.lat_ms, 0.99), 4)}
                      for p in proxies},
        "conservative_frac": rt_cfg.quota_lease_conservative_frac,
    }


# ------------------------------------------------------------- fabric
def _tiny_model():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.transformer import TransformerConfig, TransformerLM
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return cfg, params


def _mk_replica(cfg, params, rid, fabric, peers=None, summaries=None,
                spec=None):
    from ray_tpu.serve.disagg import DisaggLLMDeployment
    spec = spec or {}
    return DisaggLLMDeployment(
        cfg, n_slots=2, max_len=int(spec.get("fabric_max_len", 128)),
        prefill_chunk=8, prefill_budget=16,
        prefix_cache_slots=int(spec.get("fabric_cache_slots", 4)),
        params_fn=lambda: params, kv_fabric=fabric,
        peers=peers, summaries_fn=summaries)


def _fabric_sessions(spec, rng):
    k = int(spec.get("fabric_prefixes", 2))
    n = int(spec.get("fabric_sessions", 12))
    plen = int(spec.get("fabric_prefix_len", 33))   # 4 chunks of 8
    import numpy as np
    prefixes = [rng.integers(0, 128, size=plen) for _ in range(k)]
    out = []
    for _ in range(n):
        # uniform prefix draw: sessions sharing a prefix land on BOTH
        # replicas under round-robin routing, so cross-replica reuse
        # (the fabric's reason to exist) actually occurs
        body = np.concatenate([prefixes[int(rng.integers(k))],
                               rng.integers(0, 128, size=3)])
        out.append([int(t) for t in body])
    return out


def _drive(replicas, sessions, new_tokens):
    """Round-robin the session stream across the replicas (the sharded
    front door's routing shape) and collect cluster hit accounting."""
    outs = []
    for i, toks in enumerate(sessions):
        rep = replicas[i % len(replicas)]
        outs.append(rep.generate(toks, max_new_tokens=new_tokens))
    hits = sum(r.engine.stats().get("prefix_hits", 0) for r in replicas)
    lookups = sum(r.engine.stats().get("prefix_lookups", 0)
                  for r in replicas)
    return outs, (hits / lookups if lookups else 0.0)


def _run_fabric(spec, rng):
    from ray_tpu.inference import LLMDeployment
    cfg, params = _tiny_model()
    sessions = _fabric_sessions(spec, rng)
    new_tokens = int(spec.get("fabric_new_tokens", 8))

    # colocated oracle for the bit-identical check
    oracle = LLMDeployment(cfg, n_slots=2, max_len=128, prefill_chunk=8,
                           prefill_budget=16, prefix_cache_slots=0,
                           params_fn=lambda: params)
    want = [oracle.generate(s, max_new_tokens=new_tokens)
            for s in sessions]
    oracle.engine.stop()

    def build(fabric):
        reps = {}
        summaries = {rid: None for rid in ("A", "B")}

        def rows():
            return [{"replica_id": rid,
                     **rep.engine.prefix_cache.summary()}
                    for rid, rep in reps.items()]
        for rid in ("A", "B"):
            reps[rid] = _mk_replica(cfg, params, rid, fabric,
                                    peers=reps, summaries=rows,
                                    spec=spec)
        del summaries
        return reps

    # baseline: fabric OFF — the prefill-funnel shape degenerates to
    # every replica paying its own local prefill per prefix
    reps = build(False)
    base_outs, base_hit = _drive(list(reps.values()), sessions,
                                 new_tokens)
    for r in reps.values():
        r.engine.stop()

    reps = build(True)
    fab_outs, fab_hit = _drive(list(reps.values()), sessions, new_tokens)
    stats = {rid: r.engine.stats() for rid, r in reps.items()}
    imports = sum(r.engine.kv_imports for r in reps.values())
    fabric_counts = {
        "exports": sum(r._singleflight.exports for r in reps.values()),
        "coalesced": sum(r._singleflight.coalesced
                         for r in reps.values()),
    }
    for r in reps.values():
        r.engine.stop()
    return {
        "sessions": len(sessions),
        "replicas": 2,
        "shared_prefixes": int(spec.get("fabric_prefixes", 2)),
        "cluster_prefix_hit_rate": round(fab_hit, 4),
        "cluster_prefix_hit_rate_baseline": round(base_hit, 4),
        "hit_rate_improved": fab_hit > base_hit,
        "kv_imports": imports,
        "bit_identical": fab_outs == want and base_outs == want,
        "decode_compile_count": {
            rid: s["decode_compile_count"] for rid, s in stats.items()},
        "singleflight": fabric_counts,
    }


# ----------------------------------------------------- batched export
def _run_batched(spec, rng):
    import math

    from ray_tpu._private.data_plane import binomial_split
    cfg, params = _tiny_model()
    rep = _mk_replica(cfg, params, "A", True, spec=spec)
    try:
        toks = [int(t) for t in rng.integers(0, 128, size=33)]
        rep.generate(toks, max_new_tokens=2)        # warm the trie
        fp = rep.engine.prefix_cache.covered_fp(toks, 4)
        k = int(spec.get("concurrent_misses", 8))
        exports0 = rep.engine.kv_exports
        barrier = threading.Barrier(k)
        errs = []

        def hit(i):
            barrier.wait()
            try:
                rep.peer_export(toks, max_chunks=4, want_fp=fp,
                                node_id=f"node-{i}")
            except Exception as e:                  # pragma: no cover
                errs.append(repr(e))

        ts = [threading.Thread(target=hit, args=(i,)) for i in range(k)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        # relay hops of the broadcast tree the waiters' nodes would ride
        # (data_plane.binomial_split — store.broadcast's exact planner)
        def depth(targets):
            if not targets:
                return 0
            return 1 + max((depth(rest)
                            for _h, rest in binomial_split(targets)),
                           default=0)
        hops = depth([f"node-{i}" for i in range(k)])
        return {
            "concurrent_misses": k,
            "export_runs": rep._singleflight.exports,
            "coalesced": rep._singleflight.coalesced,
            "engine_kv_exports": rep.engine.kv_exports - exports0,
            "relay_hops_planned": hops,
            "relay_hops_bound": int(math.log2(k)) + 1,
            "relay_within_bound": hops <= int(math.log2(k)) + 1,
            "errors": errs,
        }
    finally:
        rep.engine.stop()


# ---------------------------------------------------------------- run
def run(spec):
    import numpy as np
    rng = np.random.default_rng(int(spec.get("seed", 0)))
    result = {"edge": _run_edge(spec, rng)}
    if not spec.get("skip_fabric"):
        result["fabric"] = _run_fabric(spec, rng)
    if not spec.get("skip_batched"):
        result["batched_export"] = _run_batched(spec, rng)
    e = result["edge"]
    result.update({
        "sessions": e["sessions"], "proxies": e["proxies"],
        "p99_ttft_ms": e["p99_ttft_ms"],
        "fairness_ok": e["fairness_ok"],
        "over_admission_total": e["over_admission_total"],
    })
    return result


if __name__ == "__main__":
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()   # before the first compile; children inherit
    args = sys.argv[1:]
    spec = json.loads(args[args.index("--one") + 1]) \
        if "--one" in args else {}
    print("RESULT " + json.dumps(run(spec)), flush=True)
