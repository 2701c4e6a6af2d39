"""Continuous-batching serving throughput probe (bench.py subprocess;
the serving counterpart of decode_probe.py).

Drives the slot-pool engine (ray_tpu/inference/) with a seeded Poisson
arrival process over a MIXED-length workload (prompt lengths and
max_new_tokens both vary per request), measures:

- serve_tokens_per_s: generated tokens / wall-clock from first arrival
  to last completion (median of `runs` repetitions + spread, like the
  RL ratchet),
- ttft_p50_ms / ttft_p95_ms: per-request time-to-first-token under
  those arrivals,
- static_tokens_per_s: the same request set pushed through the
  fixed-batch `make_generate_fn` path (pad every prompt to the longest,
  run every batch to the longest max_new — what the pre-engine stack
  did), recorded in the SAME entry so the artifact carries its own
  baseline,
- vs_static: continuous / static (>= 1.0 expected on mixed lengths).

Usage: python serve_probe.py --one '{"model": "tiny", "n_slots": 8,
                                     "n_requests": 24}'
       python serve_probe.py --one '{...}' --proxies 2
Prints one line: RESULT {json}

``--proxies N`` (or ``spec["proxies"]``) is the multi-proxy workload
mode: requests round-robin through N real TenantAdmission edges (the
proxy ingress gate) before reaching the engine, and the result gains a
``per_proxy`` section (requests/tokens/ttft_p95 per edge) plus
``proxy_spread`` = (max - min) / mean of per-proxy tokens — the
horizontal-edge companion of reports/edge_probe.py's quota-lease bench.

"tiny" is a CPU-sized debug config: unlike the MFU/decode probes this
one runs without a TPU (the continuous-vs-static comparison is
platform-independent), so bench.py records it every round.
"""

import dataclasses
import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _model_cfg(name):
    import jax.numpy as jnp

    from ray_tpu.models import MODEL_REGISTRY
    from ray_tpu.models.transformer import TransformerConfig
    if name == "tiny":
        # big enough that a decode step's device time dominates the
        # host-side step overhead (the regime real serving lives in);
        # small enough to compile+run in seconds on the CI CPU
        return TransformerConfig(
            vocab_size=256, d_model=256, n_layers=6, n_heads=8,
            n_kv_heads=4, d_ff=1024, max_seq_len=512, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False)
    cfg = MODEL_REGISTRY[name]
    return dataclasses.replace(cfg, param_dtype=jnp.bfloat16,
                               dtype=jnp.bfloat16, remat=False)


def _workload(spec, rng):
    """Mixed-length request set + Poisson arrival offsets (seconds).

    With ``shared_prefixes`` = K > 0 the workload models N sessions over
    K distinct system prompts: every request opens with one of K shared
    ``prefix_len``-token prefixes (chosen uniformly) followed by a short
    random suffix — the radix-cache shape (only the FIRST request per
    prefix pays its prefill)."""
    import numpy as np
    n = spec.get("n_requests", 24)
    plo, phi = spec.get("prompt_lens", [4, 48])
    nlo, nhi = spec.get("new_tokens", [8, 48])
    vocab = spec.get("vocab", 128)
    k = int(spec.get("shared_prefixes", 0))
    prefixes = []
    if k > 0:
        plen = int(spec.get("prefix_len", 64))
        prefixes = [rng.integers(0, vocab, size=plen).astype("int32")
                    for _ in range(k)]
        plo, phi = spec.get("suffix_lens", [2, 12])
    reqs = []
    for i in range(n):
        p = int(rng.integers(plo, phi + 1))
        body = rng.integers(0, vocab, size=p).astype("int32")
        if prefixes:
            body = np.concatenate([prefixes[int(rng.integers(k))], body])
        reqs.append({
            "prompt": body,
            "new": int(rng.integers(nlo, nhi + 1)),
        })
    rate = spec.get("arrival_rate_rps", 50.0)
    gaps = rng.exponential(1.0 / rate, size=n)
    arrivals = gaps.cumsum()
    arrivals[0] = 0.0
    return reqs, arrivals


def _run_continuous(engine, reqs, arrivals, edges=None):
    """Submit at Poisson offsets; returns (tokens_per_s, handles).

    With ``edges`` (a list of real TenantAdmission gates — the
    multi-proxy mode), request i enters through edge ``i % N`` first
    and holds its concurrency lease until its stream drains, exactly
    like HttpProxy does; quotas are unlimited so admission adds its
    true per-request cost without shedding anything."""
    handles = [None] * len(reqs)
    leases = [None] * len(reqs)

    def submitter():
        t0 = time.perf_counter()
        for i, (r, at) in enumerate(zip(reqs, arrivals)):
            delay = at - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            if edges:
                leases[i] = edges[i % len(edges)].acquire("default")
            handles[i] = engine.submit(r["prompt"],
                                       max_new_tokens=r["new"])
    t_start = time.perf_counter()
    th = threading.Thread(target=submitter)
    th.start()
    th.join()
    counts = []
    for i, h in enumerate(handles):
        counts.append(len(h.tokens()))    # drains to completion
        if leases[i] is not None:
            leases[i].release()
    wall = time.perf_counter() - t_start
    return sum(counts) / wall, handles, counts


def _ttfts_ms(handles):
    return [h.ttft_s * 1000.0 for h in handles if h.ttft_s is not None]


def _p(vals, q):
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(len(vals) * q))] if vals else 0.0


def _run_static(model, params, mesh, reqs, n_slots, vocab):
    """Fixed-batch baseline: batches of n_slots in arrival order, every
    prompt padded to the set's longest, every batch decoded to the
    longest max_new. Useful tokens = what each request asked for."""
    import jax
    import numpy as np

    from ray_tpu.models.generate import make_generate_fn
    prompt_len = max(len(r["prompt"]) for r in reqs)
    max_new = max(r["new"] for r in reqs)
    _, gen_fn, _ = make_generate_fn(model, mesh, batch=n_slots,
                                    prompt_len=prompt_len,
                                    max_new_tokens=max_new)
    batch_tok = np.zeros((n_slots, prompt_len), np.int32)
    gen_fn(params, batch_tok, jax.random.PRNGKey(0))   # compile
    t0 = time.perf_counter()
    useful = 0
    for lo in range(0, len(reqs), n_slots):
        group = reqs[lo:lo + n_slots]
        batch_tok = np.zeros((n_slots, prompt_len), np.int32)
        for j, r in enumerate(group):
            # left-pad-free: static batching pads the tail; positions
            # beyond the real prompt just echo token 0 — cost model is
            # identical and that's all this baseline measures
            batch_tok[j, :len(r["prompt"])] = r["prompt"]
        np.asarray(gen_fn(params, batch_tok, jax.random.PRNGKey(1)))
        useful += sum(r["new"] for r in group)
    wall = time.perf_counter() - t0
    return useful / wall


def _run_disagg(model, params, spec, reqs, arrivals, n_slots, max_len,
                prefill_chunk, cache_slots):
    """Disaggregated split (serve/disagg.py, engine-level): a prefill
    engine fills KV blocks, a decode engine imports them through the
    real wire framing (pack/unpack round-trip) and serves the Poisson
    stream. Returns per-run rates + decode-engine stats + hand-off
    accounting (count, payload bytes, and the fp16-framing bytes the
    same spans would have cost — with ``kv_quant: "int8"`` the saving
    is the wire half of the int8 win) — recorded next to the colocated
    number in the SAME entry."""
    import numpy as np

    from ray_tpu.inference import EngineConfig, InferenceEngine
    from ray_tpu.serve.disagg import pack_kv_spans, unpack_kv_spans

    def mk(slots, pslots):
        return InferenceEngine(
            model, params,
            EngineConfig(n_slots=slots, max_len=max_len,
                         prefill_chunk=prefill_chunk,
                         prefill_budget=spec.get("prefill_budget",
                                                 2 * prefill_chunk),
                         kv_quant=spec.get("kv_quant", "none"),
                         prefix_cache_slots=pslots)).start()

    pslots = max(1, int(cache_slots))
    prefill = mk(2, pslots)
    decode = mk(n_slots, pslots)
    list(prefill.submit(reqs[0]["prompt"][:4], max_new_tokens=1))
    list(decode.submit(reqs[0]["prompt"][:4], max_new_tokens=2))
    C = prefill_chunk
    handoffs = [0]
    wire = {"payload_bytes": 0, "fp16_bytes": 0}

    def submit_one(r):
        toks = [int(t) for t in r["prompt"]]
        want = (max(0, len(toks) - 1) // C) * C
        if want and decode.prefix_cache.peek(toks) < want:
            # cold on the decode tier: prefill-tier fill + hand-off
            if prefill.prefix_cache.peek(toks) < want:
                for _ in prefill.submit(toks, max_new_tokens=1):
                    pass
            covered, spans = prefill.export_kv_blocks(toks)
            if covered:
                payload = pack_kv_spans(spans)
                decode.import_kv_blocks(toks[:covered],
                                        unpack_kv_spans(payload))
                handoffs[0] += 1
                wire["payload_bytes"] += len(payload)
                wire["fp16_bytes"] += sum(
                    (np.asarray(s[0]).size + np.asarray(s[1]).size) * 2
                    for s in spans)
        return decode.submit(toks, max_new_tokens=r["new"])

    rates = []
    for _ in range(spec.get("runs", 3)):
        handles = [None] * len(reqs)
        t0 = time.perf_counter()
        for i, (r, at) in enumerate(zip(reqs, arrivals)):
            delay = at - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            handles[i] = submit_one(r)
        total = sum(len(h.tokens()) for h in handles)
        rates.append(total / (time.perf_counter() - t0))
    stats = decode.stats()
    prefill.stop()
    decode.stop()
    rates.sort()
    return rates, stats, handoffs[0], wire


def run(spec):
    import jax
    import numpy as np

    from ray_tpu.inference import EngineConfig, InferenceEngine
    from ray_tpu.models import TransformerLM
    from ray_tpu.parallel import MeshConfig, make_mesh

    cfg = _model_cfg(spec.get("model", "tiny"))
    spec.setdefault("vocab", min(cfg.vocab_size, 128))
    model = TransformerLM(cfg)
    n_slots = spec.get("n_slots", 8)
    max_len = spec.get("max_len", min(256, cfg.max_seq_len))
    prefill_chunk = spec.get("prefill_chunk", 32)
    shared_k = int(spec.get("shared_prefixes", 0))
    # prefix workload: enough cache slots that every distinct shared
    # prefix fits (K * prefix_len tokens of blocks), unless pinned
    cache_slots = spec.get("prefix_cache_slots")
    if cache_slots is None:
        cache_slots = 0
        if shared_k:
            plen = int(spec.get("prefix_len", 64))
            cache_slots = max(1, -(-shared_k * plen // max_len))
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]

    def build_engine(prefix_slots):
        eng = InferenceEngine(
            model, params,
            EngineConfig(n_slots=n_slots, max_len=max_len,
                         prefill_chunk=prefill_chunk,
                         prefill_budget=spec.get("prefill_budget",
                                                 2 * prefill_chunk),
                         prefix_cache_slots=prefix_slots))
        return eng.start()

    engine = build_engine(int(cache_slots))
    rng = np.random.default_rng(spec.get("seed", 0))
    reqs, arrivals = _workload(spec, rng)

    # warmup: compile all engine programs on a short request
    list(engine.submit(reqs[0]["prompt"][:4], max_new_tokens=2))

    n_proxies = int(spec.get("proxies", 0))
    edges = None
    if n_proxies >= 2:
        from ray_tpu.serve.fleet import TenantAdmission
        edges = [TenantAdmission(default_quota=0)
                 for _ in range(n_proxies)]

    rates, all_handles, all_counts = [], [], []
    for _ in range(spec.get("runs", 3)):
        rate, handles, counts = _run_continuous(engine, reqs, arrivals,
                                                edges=edges)
        rates.append(rate)
        all_handles.extend(handles)
        all_counts.extend(counts)
    stats = engine.stats()
    compile_count = stats["decode_compile_count"]
    engine.stop()

    mesh = make_mesh(MeshConfig(data=1, fsdp=1, seq=1, tensor=1),
                     devices=jax.devices()[:1])
    static_rate = _run_static(model, params, mesh, reqs, n_slots,
                              spec["vocab"])

    rates.sort()
    med = rates[len(rates) // 2]
    spread = (rates[-1] - rates[0]) / med if med else 0.0
    all_ttfts = sorted(_ttfts_ms(all_handles))
    result = {
        "model": spec.get("model", "tiny"), "n_slots": n_slots,
        "max_len": max_len, "n_requests": len(reqs),
        "arrival_rate_rps": spec.get("arrival_rate_rps", 50.0),
        "serve_tokens_per_s": round(med, 1),
        "spread": round(spread, 3),
        "runs": [round(r, 1) for r in rates],
        "ttft_p50_ms": round(_p(all_ttfts, 0.50), 1),
        "ttft_p95_ms": round(_p(all_ttfts, 0.95), 1),
        "static_tokens_per_s": round(static_rate, 1),
        "vs_static": round(med / static_rate, 3) if static_rate else None,
        "decode_compile_count": compile_count,
    }
    if edges:
        # per-proxy spread: the round-robin edge assignment repeats
        # each run, so global handle index modulo the request count
        # recovers request index, and THAT modulo N the proxy
        per = {}
        per_tokens = []
        for j in range(n_proxies):
            mine = [g for g in range(len(all_handles))
                    if (g % len(reqs)) % n_proxies == j]
            hs = [all_handles[g] for g in mine]
            toks = sum(all_counts[g] for g in mine)
            ts = sorted(_ttfts_ms(hs))
            per[f"p{j}"] = {
                "requests": len(hs), "tokens": toks,
                "admitted": edges[j].admitted_total.get("default", 0),
                "shed": sum(edges[j].shed_total.values()),
                "ttft_p95_ms": round(_p(ts, 0.95), 1)}
            per_tokens.append(toks)
        mean_tok = sum(per_tokens) / len(per_tokens)
        result.update({
            "proxies": n_proxies,
            "per_proxy": per,
            "proxy_spread": round(
                (max(per_tokens) - min(per_tokens)) / mean_tok, 3)
            if mean_tok else None,
        })
    if shared_k:
        # hit/miss TTFT split (the radix cache's reason to exist: a hit
        # skips the shared prefix's prefill entirely) + the same
        # workload through a cache-DISABLED engine in the same entry
        hit = _ttfts_ms([h for h in all_handles if h.prefix_matched])
        miss = _ttfts_ms([h for h in all_handles if not h.prefix_matched])
        p95_hit, p95_miss = _p(hit, 0.95), _p(miss, 0.95)
        base = build_engine(0)
        list(base.submit(reqs[0]["prompt"][:4], max_new_tokens=2))
        base_rates = []
        for _ in range(spec.get("runs", 3)):
            r0, _h, _c = _run_continuous(base, reqs, arrivals)
            base_rates.append(r0)
        base.stop()
        base_rates.sort()
        base_med = base_rates[len(base_rates) // 2]
        result.update({
            "shared_prefixes": shared_k,
            "prefix_len": int(spec.get("prefix_len", 64)),
            "prefix_cache_slots": int(cache_slots),
            "prefix_hit_rate": stats.get("prefix_hit_rate", 0.0),
            "prefix_tokens_saved": stats.get("prefix_tokens_saved", 0),
            "ttft_p95_hit_ms": round(p95_hit, 1),
            "ttft_p95_miss_ms": round(p95_miss, 1),
            "ttft_hit_vs_miss_p95": round(p95_hit / p95_miss, 3)
            if p95_miss else None,
            "no_prefix_tokens_per_s": round(base_med, 1),
            "vs_no_prefix": round(med / base_med, 3) if base_med else None,
        })
    if spec.get("disagg"):
        # disagg-vs-colocated split (ROADMAP item 1): the same workload
        # through a prefill-tier/decode-tier pair with real KV hand-off
        # framing, recorded next to the colocated median above. The
        # colocated figure above never changes with kv_quant — only the
        # disagg tiers opt in, keeping serve_tokens_per_s ratchet-
        # comparable across rounds.
        d_rates, d_stats, handoffs, wire = _run_disagg(
            model, params, spec, reqs, arrivals, n_slots, max_len,
            prefill_chunk, cache_slots or 2)
        d_med = d_rates[len(d_rates) // 2]
        lookups = d_stats.get("prefix_lookups", 0)
        result.update({
            "disagg_tokens_per_s": round(d_med, 1),
            "disagg_runs": [round(r, 1) for r in d_rates],
            "vs_colocated": round(d_med / med, 3) if med else None,
            "kv_handoffs": handoffs,
            "kv_imports": d_stats.get("kv_imports", 0),
            "remote_prefix_tokens": d_stats.get("remote_prefix_tokens", 0),
            # fraction of decode-tier admissions that skipped prefill via
            # the combined local+imported cache — N caches as one
            "cluster_prefix_hit_rate": round(
                d_stats.get("prefix_hits", 0) / lookups, 4)
            if lookups else 0.0,
            "disagg_decode_compile_count":
                d_stats.get("decode_compile_count"),
            "kv_handoff_payload_bytes": wire["payload_bytes"],
            "kv_handoff_fp16_bytes": wire["fp16_bytes"],
        })
        if spec.get("kv_quant", "none") != "none":
            saved = wire["fp16_bytes"] - wire["payload_bytes"]
            result.update({
                "kv_quant": spec["kv_quant"],
                "kv_handoff_bytes_saved_vs_fp16": saved,
                "kv_handoff_wire_ratio_vs_fp16": round(
                    wire["payload_bytes"] / wire["fp16_bytes"], 3)
                if wire["fp16_bytes"] else None,
                "kv_quant_slot_gain_vs_fp16":
                    d_stats.get("kv_quant_slot_gain_vs_fp16"),
            })
    if spec.get("sharded"):
        # sharded-replica figure as its OWN nested entry (the colocated
        # single-device serve_tokens_per_s above stays untouched for the
        # vs_r05_ratchet comparison; reports/sharded_probe.py owns the
        # methodology)
        _here = os.path.dirname(os.path.abspath(__file__))
        if _here not in sys.path:
            sys.path.insert(0, _here)
        import sharded_probe
        result["sharded"] = sharded_probe.run(dict(
            spec.get("sharded") if isinstance(spec.get("sharded"), dict)
            else {}))
    return result


if __name__ == "__main__":
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()   # before the first compile; children inherit
    args = sys.argv[1:]
    spec = json.loads(args[args.index("--one") + 1]) \
        if "--one" in args else {}
    if "--sharded" in args:
        spec.setdefault("sharded", True)
    if "--proxies" in args:
        spec.setdefault("proxies", int(args[args.index("--proxies") + 1]))
    print("RESULT " + json.dumps(run(spec)), flush=True)
