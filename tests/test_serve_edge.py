"""Cluster serving edge (ROADMAP item 2): shared tenant-quota leases
across proxies, the decode→decode KV fabric with its fallback ladder,
batched hot-prefix export coalescing, and per-tenant SLO burn.

Everything here is hermetic: the GCS lease handlers run on a bare
GcsServer instance, the lease client gets a fake clock + in-process
call shim, and the fabric tests wire DisaggLLMDeployment peers as
direct objects (the same injection seams the cluster path uses)."""

import threading

import numpy as np
import pytest

from ray_tpu._private.config import cfg as rt_cfg
from ray_tpu.serve.fleet import (QuotaLeaseClient, TenantAdmission,
                                 TenantQuotaExceeded, TenantTokenBucket)


# ==========================================================================
# TenantTokenBucket: leased-share refill arithmetic (fake clock)
# ==========================================================================

def test_bucket_burst_drain_refill_and_deficit():
    b = TenantTokenBucket(rate=2.0, burst=4.0, now=0.0)
    assert [b.take(0.0) for _ in range(4)] == [True] * 4
    assert not b.take(0.0)                   # burst exhausted
    # the honest Retry-After: (1 - tokens) / rate
    assert b.wait_s(0.0) == pytest.approx(0.5)
    assert b.take(0.5)                       # exactly one token refilled
    assert not b.take(0.5)
    b2 = TenantTokenBucket(rate=2.0, burst=4.0, now=0.0)
    for _ in range(4):
        b2.take(0.0)
    assert b2.take(10.0)                     # refill caps at burst
    assert b2.tokens == pytest.approx(3.0)


def test_bucket_unlimited_and_set_params_clamp():
    b = TenantTokenBucket(rate=0.0, burst=1.0)
    assert all(b.take(0.0) for _ in range(100))   # rate<=0 = unlimited
    assert b.wait_s(0.0) == 0.0
    b = TenantTokenBucket(rate=4.0, burst=8.0, now=0.0)
    b.set_params(1.0, 2.0)                   # re-split shrank the share
    assert b.tokens == 2.0                   # banked tokens clamp to burst
    assert b.burst == 2.0 and b.rate == 1.0


# ==========================================================================
# GCS lease handlers: split, epoch, escrow
# ==========================================================================

def _gcs():
    from ray_tpu._private.gcs import GcsServer
    g = GcsServer.__new__(GcsServer)
    g.tenant_quotas = {}
    g.quota_leases = {}
    g.quota_lease_epoch = 1
    g.tenant_burn = {}
    return g


def _call(g):
    return lambda method, **kw: getattr(g, "h_" + method)(None, **kw)


def test_gcs_lease_acquire_splits_rate_and_bumps_epoch():
    g = _gcs()
    assert g.h_set_tenant_quota(None, "a", rate=10.0, burst=10.0)
    e0 = g.quota_lease_epoch
    out1 = g.h_quota_lease_acquire(None, "p1")
    assert out1["epoch"] == e0 + 1 and out1["n_proxies"] == 1
    assert out1["shares"]["a"]["rate"] == pytest.approx(10.0)
    out2 = g.h_quota_lease_acquire(None, "p2")
    assert out2["epoch"] == e0 + 2 and out2["n_proxies"] == 2
    assert out2["shares"]["a"]["rate"] == pytest.approx(5.0)
    assert out2["shares"]["a"]["cluster_rate"] == pytest.approx(10.0)
    # stale-epoch renew gets the fresh split piggybacked; current-epoch
    # renew stays lean (no shares payload)
    r = g.h_quota_lease_renew(None, "p1", epoch=out1["epoch"])
    assert not r["revoked"] and r["shares"]["a"]["rate"] == \
        pytest.approx(5.0)
    r2 = g.h_quota_lease_renew(None, "p1", epoch=r["epoch"])
    assert "shares" not in r2
    # a rate change bumps the epoch so proxies re-split on next renew
    g.h_set_tenant_quota(None, "a", rate=20.0)
    assert g.quota_lease_epoch == out2["epoch"] + 1


def test_gcs_lease_revoke_escrows_share():
    g = _gcs()
    g.h_set_tenant_quota(None, "a", rate=10.0, burst=10.0)
    g.h_quota_lease_acquire(None, "p1")
    e = g.h_quota_lease_acquire(None, "p2")["epoch"]
    assert g.h_quota_lease_revoke(None, "p1")
    assert not g.h_quota_lease_revoke(None, "nobody")
    # the ESCROW property: p1 still counts in the denominator, so p2's
    # share must NOT grow while p1 may still be admitting
    r = g.h_quota_lease_renew(None, "p2", epoch=e)   # stale → shares
    assert r["shares"]["a"]["rate"] == pytest.approx(5.0)
    # the revoked proxy learns on its renew and must degrade
    assert g.h_quota_lease_renew(None, "p1", epoch=e)["revoked"]
    # re-acquire clears the revocation and restores the full share
    out = g.h_quota_lease_acquire(None, "p1")
    assert out["shares"]["a"]["rate"] == pytest.approx(5.0)
    st = g.h_quota_lease_status(None)
    assert all(not row["revoked"] for row in st["leases"])


def test_gcs_lease_release_prune_and_burn_fold():
    g = _gcs()
    g.h_set_tenant_quota(None, "a", rate=8.0)
    g.h_quota_lease_acquire(None, "p1")
    e = g.h_quota_lease_acquire(None, "p2")["epoch"]
    g.h_quota_lease_renew(None, "p1", epoch=e, burn={"a": 3})
    g.h_quota_lease_renew(None, "p2", epoch=e, burn={"a": 2, "b": 1})
    st = g.h_quota_lease_status(None)
    assert st["tenant_burn"] == {"a": 5, "b": 1}
    assert g.h_quota_lease_release(None, "p2")
    assert g.quota_lease_epoch > e
    # an expired lease prunes out (and bumps the epoch) on any touch
    g.quota_leases["p1"]["ts"] -= rt_cfg.quota_lease_ttl_s + 1
    st = g.h_quota_lease_status(None)
    assert st["leases"] == []


# ==========================================================================
# QuotaLeaseClient against the real handlers (fake clock)
# ==========================================================================

class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture()
def fast_renew():
    rt_cfg.set("quota_lease_interval_s", 0.0)
    try:
        yield
    finally:
        rt_cfg.reset("quota_lease_interval_s")


def test_lease_client_admit_burn_and_retry_hint(fast_renew):
    g = _gcs()
    g.h_set_tenant_quota(None, "a", rate=2.0, burst=2.0)
    clk = _Clock()
    c = QuotaLeaseClient("p1", _call(g), clock=clk)
    assert c.acquire()
    assert c.admit("a", clk()) is None
    assert c.admit("a", clk()) is None       # burst of 2
    wait = c.admit("a", clk())
    assert wait is not None and wait == pytest.approx(0.5, abs=0.01)
    assert c.retry_hint("a") == pytest.approx(wait, abs=0.01)
    assert c.retry_hint("unrated") is None
    # burn deltas reach the GCS cluster totals via renew
    clk.t += 0.01
    c.maybe_renew(clk())
    assert g.tenant_burn.get("a") == 2
    assert c.stats()["pending_burn"] == {}


def test_lease_client_adopts_resplit_on_epoch_move(fast_renew):
    g = _gcs()
    g.h_set_tenant_quota(None, "a", rate=10.0, burst=10.0)
    clk = _Clock()
    c1 = QuotaLeaseClient("p1", _call(g), clock=clk)
    assert c1.acquire()
    assert c1.stats()["rates"]["a"] == pytest.approx(10.0)
    g.h_quota_lease_acquire(None, "p2")      # second proxy joins
    clk.t += 0.01
    c1.maybe_renew(clk())                    # stale epoch → re-split
    assert c1.stats()["rates"]["a"] == pytest.approx(5.0)


def test_lease_client_revoked_degrades_then_reacquires(fast_renew):
    g = _gcs()
    g.h_set_tenant_quota(None, "a", rate=8.0, burst=8.0)
    clk = _Clock()
    c1 = QuotaLeaseClient("p1", _call(g), clock=clk)
    c2 = QuotaLeaseClient("p2", _call(g), clock=clk)
    assert c1.acquire() and c2.acquire()
    clk.t += 0.01
    c1.maybe_renew(clk())                    # adopt the 2-proxy re-split
    share = c1.stats()["rates"]["a"]
    assert share == pytest.approx(4.0)
    g.h_quota_lease_revoke(None, "p1")
    clk.t += 0.01
    c1.maybe_renew(clk())                    # learns the revocation
    assert c1.revoked
    frac = rt_cfg.quota_lease_conservative_frac
    assert c1.stats()["rates"]["a"] == pytest.approx(share * frac)
    # survivor's share is UNCHANGED (escrow): degraded + survivor stays
    # strictly under the cluster rate → no over-admission window
    clk.t += 0.01
    c2.maybe_renew(clk())
    assert c2.stats()["rates"]["a"] == pytest.approx(4.0)
    assert c1.stats()["rates"]["a"] + c2.stats()["rates"]["a"] < 8.0
    # next tick re-acquires and restores the full split
    clk.t += 0.01
    c1.maybe_renew(clk())
    assert not c1.revoked
    assert c1.stats()["rates"]["a"] == pytest.approx(4.0)


def test_lease_client_renew_failure_rebanks_burn_and_degrades(fast_renew):
    g = _gcs()
    g.h_set_tenant_quota(None, "a", rate=4.0, burst=4.0)
    clk = _Clock()
    state = {"fail": False}
    real = _call(g)

    def call(method, **kw):
        if state["fail"] and method == "quota_lease_renew":
            raise ConnectionError("gcs away")
        return real(method, **kw)

    c = QuotaLeaseClient("p1", call, clock=clk)
    assert c.acquire()
    assert c.admit("a", clk()) is None
    state["fail"] = True
    clk.t += 0.01
    c.maybe_renew(clk())                     # renew fails → burn re-banked
    assert c.stats()["pending_burn"] == {"a": 1}
    assert not c.revoked                     # inside the TTL: full share
    clk.t += rt_cfg.quota_lease_ttl_s + 1.0
    c.maybe_renew(clk())                     # past TTL: degrade
    assert c.revoked
    frac = rt_cfg.quota_lease_conservative_frac
    assert c.stats()["rates"]["a"] == pytest.approx(4.0 * frac)


# ==========================================================================
# Chaos: QuotaLeaseRevoker round-trip (satellite 6)
# ==========================================================================

def test_quota_lease_revoker_no_over_admission(fast_renew):
    from ray_tpu.util.chaos import QuotaLeaseRevoker
    g = _gcs()
    g.h_set_tenant_quota(None, "hot", rate=10.0, burst=10.0)
    clk = _Clock()
    clients = {p: QuotaLeaseClient(p, _call(g), clock=clk)
               for p in ("p1", "p2")}
    for c in clients.values():
        assert c.acquire()
    clk.t += 0.01
    for c in clients.values():
        c.maybe_renew(clk())                 # both adopt the 2-way split
    rev = QuotaLeaseRevoker(_call(g), seed=7)
    assert sorted(rev.lease_ids()) == ["p1", "p2"]
    pid = rev.revoke_one()
    assert pid in clients and rev.revoked == [pid]
    victim, survivor = clients[pid], \
        clients[{"p1": "p2", "p2": "p1"}[pid]]

    def poke():
        clk.t += 0.01
        victim.maybe_renew(clk())
        survivor.maybe_renew(clk())

    assert rev.wait_for_degraded(victim, timeout_s=5.0, poke=poke)
    frac = rt_cfg.quota_lease_conservative_frac
    # the invariant: degraded victim + escrow-frozen survivor admit
    # strictly under the cluster rate throughout the window
    assert victim.stats()["rates"]["hot"] == pytest.approx(5.0 * frac)
    assert survivor.stats()["rates"]["hot"] == pytest.approx(5.0)
    assert (victim.stats()["rates"]["hot"]
            + survivor.stats()["rates"]["hot"]) < 10.0
    # and the round-trip: the victim re-leases back to a full share
    assert rev.wait_for_release(victim, timeout_s=5.0, poke=poke)
    assert victim.stats()["rates"]["hot"] == pytest.approx(5.0)


# ==========================================================================
# Satellite 1: Retry-After derives from the bucket deficit
# ==========================================================================

def test_shed_retry_after_uses_bucket_deficit():
    adm = TenantAdmission(default_quota=1, queue_max=0)
    adm.retry_hint = lambda t: 2.5           # the lease client's deficit
    lease = adm.acquire("a")
    with pytest.raises(TenantQuotaExceeded) as ei:
        adm.acquire("a")
    assert ei.value.retry_after_s == pytest.approx(2.5)
    lease.release()
    # a broken/None hint falls back to the fixed constant
    adm.retry_hint = lambda t: (_ for _ in ()).throw(RuntimeError())
    lease = adm.acquire("a")
    with pytest.raises(TenantQuotaExceeded) as ei:
        adm.acquire("a")
    assert ei.value.retry_after_s == pytest.approx(
        rt_cfg.tenant_retry_after_s)
    lease.release()


# ==========================================================================
# Per-tenant SLO burn rows (ROADMAP item 2d)
# ==========================================================================

def test_evaluate_tenant_slo_rows_and_unseen_skip():
    from ray_tpu.serve.slo import evaluate_tenant_slo
    samples = {"a": 0.2, "b": None}          # b: no observations at all

    def query(metric, window=60.0, agg="avg", tags=None, threshold=None):
        assert metric == "serve_tenant_ttft_ms" and agg == "frac_over"
        return {"value": samples[tags["tenant"]]}

    slo = {"p95_ttft_ms": 100.0, "budget_fraction": 0.05}
    rows = evaluate_tenant_slo(slo, query, ["a", "b"])
    assert len(rows) == 1                    # absent != violating
    row = rows[0]
    assert row["tenant"] == "a" and row["objective"] == "tenant_latency"
    assert row["burn_fast"] == pytest.approx(0.2 / 0.05)
    assert row["violating"]
    assert evaluate_tenant_slo({}, query, ["a"]) == []
    assert evaluate_tenant_slo(slo, query, []) == []


# ==========================================================================
# KV fabric: decode→decode hand-off + fallback ladder (engine-backed)
# ==========================================================================

@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, TransformerLM
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _mk_dep(tiny_fixture, **kw):
    from ray_tpu.serve.disagg import DisaggLLMDeployment
    cfg, _model, params = tiny_fixture
    args = dict(n_slots=2, max_len=64, prefill_chunk=4, prefill_budget=8,
                prefix_cache_slots=2, params_fn=lambda: params)
    args.update(kw)
    return DisaggLLMDeployment(cfg, **args)


def _oracle(tiny_fixture, prompt, n=10, **kw):
    from ray_tpu.inference import LLMDeployment
    cfg, _model, params = tiny_fixture
    args = dict(n_slots=2, max_len=64, prefill_chunk=4,
                prefill_budget=8, prefix_cache_slots=0,
                params_fn=lambda: params)
    args.update(kw)
    dep = LLMDeployment(cfg, **args)
    try:
        return dep.generate(prompt, max_new_tokens=n)
    finally:
        dep.engine.stop()


def _rows(rid, dep):
    return lambda: [{"replica_id": rid,
                     **dep.engine.prefix_cache.summary()}]


PROMPT = list(range(50, 67))                 # 17 tokens: 4 full chunks


def test_fabric_peer_import_bit_identical_and_compile_once(tiny):
    a = _mk_dep(tiny)
    b = _mk_dep(tiny, peers={"A": a}, summaries_fn=_rows("A", a))
    try:
        want = _oracle(tiny, PROMPT)
        a.generate(PROMPT, max_new_tokens=2)   # warm the peer's trie
        got = b.generate(PROMPT, max_new_tokens=10)
        assert got == want                     # greedy bit-identical
        assert b.engine.kv_imports == 1
        assert b.engine.remote_prefix_tokens == 16
        assert b.engine.decode_compile_count == 1
        assert a._singleflight.exports == 1
        # second request: local radix hit, no new fabric pull
        assert b.generate(PROMPT, max_new_tokens=10) == want
        assert b.engine.kv_imports == 1
        assert b.engine.sched.queue_depth() == 0
    finally:
        a.engine.stop()
        b.engine.stop()


def test_fabric_peer_dead_mid_export_lands_on_local_prefill(tiny):
    from ray_tpu.util.chaos import PeerExportKiller
    a = _mk_dep(tiny)
    b = _mk_dep(tiny, peers={"A": a}, summaries_fn=_rows("A", a))
    killer = PeerExportKiller(1.0)
    try:
        want = _oracle(tiny, PROMPT, n=8)
        a.generate(PROMPT, max_new_tokens=2)
        killer.arm_local()
        with pytest.raises(Exception):
            a.peer_export(PROMPT)              # the injection really fires
        got = b.generate(PROMPT, max_new_tokens=8)
        assert got == want                     # rung 5, exactly-once
        assert b.engine.kv_imports == 0
        assert b.engine.sched.queue_depth() == 0
    finally:
        killer.disarm_local()
        a.engine.stop()
        b.engine.stop()


def test_fabric_stale_fingerprint_lands_on_local_prefill(tiny):
    from ray_tpu.inference.prefix_cache import chunk_fingerprints
    a = _mk_dep(tiny)
    # the summary CLAIMS coverage the live trie never had — the shape of
    # "summary newer than evicted blocks": the exporter must refuse
    fake = [{"replica_id": "A", "chunk": 4,
             "fps": chunk_fingerprints(PROMPT, 4, max_chunks=4)}]
    b = _mk_dep(tiny, peers={"A": a}, summaries_fn=lambda: fake)
    try:
        want = _oracle(tiny, PROMPT, n=8)
        got = b.generate(PROMPT, max_new_tokens=8)
        assert got == want
        assert b.engine.kv_imports == 0
        # and the explicit proof path: a cached prefix with the WRONG
        # requested fingerprint refuses with the stale diagnosis
        a.generate(PROMPT, max_new_tokens=2)
        with pytest.raises(LookupError, match="stale fingerprint"):
            a.peer_export(PROMPT, max_chunks=4, want_fp=0x1234)
    finally:
        a.engine.stop()
        b.engine.stop()


def test_fabric_quant_mismatch_refuses_lossy_direction(tiny):
    # int8 wire -> fp pool is the one LOSSY direction; the fabric must
    # refuse it and land on local prefill so greedy stays bit-identical
    a = _mk_dep(tiny, kv_quant="int8")
    b = _mk_dep(tiny, peers={"A": a}, summaries_fn=_rows("A", a))
    try:
        want = _oracle(tiny, PROMPT, n=8)
        a.generate(PROMPT, max_new_tokens=2)
        got = b.generate(PROMPT, max_new_tokens=8)
        assert got == want
        assert b.engine.kv_imports == 0        # refused, not imported
    finally:
        a.engine.stop()
        b.engine.stop()


def test_fabric_fp_wire_into_int8_pool_imports_exactly(tiny):
    # fp wire -> int8 pool quantizes with the save-path math: the import
    # is exact vs what the int8 engine would have produced locally
    a = _mk_dep(tiny)
    b = _mk_dep(tiny, kv_quant="int8", peers={"A": a},
                summaries_fn=_rows("A", a))
    try:
        want = _oracle(tiny, PROMPT, n=8, kv_quant="int8",
                       prefix_cache_slots=2)
        a.generate(PROMPT, max_new_tokens=2)
        got = b.generate(PROMPT, max_new_tokens=8)
        assert got == want
        assert b.engine.kv_imports == 1
    finally:
        a.engine.stop()
        b.engine.stop()


def test_batched_export_single_flight_coalesces(tiny):
    a = _mk_dep(tiny)
    try:
        a.generate(PROMPT, max_new_tokens=2)
        fp = a.engine.prefix_cache.covered_fp(PROMPT, 4)
        assert fp is not None
        exports0 = a.engine.kv_exports
        barrier = threading.Barrier(8)
        outs, errs = [], []

        def hit(i):
            barrier.wait()
            try:
                outs.append(a.peer_export(PROMPT, max_chunks=4,
                                          want_fp=fp, node_id=f"n{i}"))
            except Exception as e:           # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=hit, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert not errs
        # the acceptance bound: 8 concurrent misses, exactly 1 export
        assert len(outs) == 8
        assert all(o["covered"] == 16 for o in outs)
        assert a._singleflight.exports == 1
        assert a._singleflight.coalesced == 7
        assert a.engine.kv_exports == exports0 + 1
    finally:
        a.engine.stop()


# ==========================================================================
# serve_million_sessions smoke (scaled down: 1k of the probe's 100k sessions)
# ==========================================================================

@pytest.mark.slow
def test_serve_million_sessions_smoke():
    """O(1k)-session edge_probe pass through 2 real proxies: exercises
    the full wiring of the serving edge (quota leases + revocation,
    KV fabric vs local-only baseline, coalesced batched export) without
    the 100k-session figure run."""
    from tests import edge_probe
    # cluster rate scales down with the session count so the buckets
    # actually constrain (at the default 2000/s a 1k run never sheds
    # and the raw zipf draw leaks past the fairness bound) and so the
    # revoked proxy's degrade->restore round trip lands inside the run
    res = edge_probe.run({"n_sessions": 1000, "proxies": 2, "seed": 0,
                          "cluster_rate_rps": 10.0})
    assert res["sessions"] == 1000
    assert res["proxies"] == 2
    assert res["fairness_ok"]
    assert res["over_admission_total"] == 0
    edge = res["edge"]
    assert edge["degraded_after_sessions"] is not None
    assert edge["restored_after_sessions"] is not None
    fab = res["fabric"]
    assert fab["hit_rate_improved"]
    assert fab["bit_identical"]
    assert all(c == 1 for c in fab["decode_compile_count"].values())
    bat = res["batched_export"]
    assert bat["export_runs"] == 1
    assert bat["coalesced"] == 7
    assert bat["relay_within_bound"]
    assert not bat["errors"]


# ==========================================================================
# rtlint: self-gate over the cluster-edge modules
# ==========================================================================

def test_rtlint_clean_on_edge_modules():
    """The edge stack (quota leases, KV fabric, chaos, edge probe)
    ships lint-clean: a full rtlint pass — all rules, NO baseline —
    over every module this plane touches reports zero findings."""
    import os

    from ray_tpu.devtools.lint import run_lint
    from ray_tpu.devtools.lint.config import LintConfig
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    targets = [os.path.join(repo, *p.split("/")) for p in (
        "ray_tpu/serve/fleet.py", "ray_tpu/serve/proxy.py",
        "ray_tpu/serve/disagg.py", "ray_tpu/serve/slo.py",
        "ray_tpu/util/chaos.py", "tests/edge_probe.py")]
    r = run_lint(targets, config=LintConfig(root=repo),
                 use_baseline=False)
    assert r.findings == [], [str(f) for f in r.findings]
