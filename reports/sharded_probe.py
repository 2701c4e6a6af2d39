"""Sharded serving-plane probe (bench.py subprocess): speculative
decoding + int8 KV through the real ShardedEngineReplica path.

Measures, in ONE entry (so the artifact carries its own baseline):

- sharded_decode_tokens_per_s: generated tokens / wall-clock for a
  request set served through a spec-decode-ON ShardedEngineReplica
  (median of `runs` + spread),
- tokens_per_s_per_chip: the same rate / device count — the figure that
  must hold up as the gang widens,
- spec_decode_accept_rate: accepted / proposed draft tokens,
- no_spec_tokens_per_s + vs_no_spec: the identical workload through a
  spec-OFF replica (same params, same seed) — the raw-speed multiplier
  itself, expected > 1.0,
- compile-once evidence: decode_compile_count and
  spec_verify_compile_count from the engine.

Draft policy: by default the draft IS the target ("self"-draft via
``draft_params_fn``), which pins the accept rate at its 1.0 upper bound
and isolates the mechanism the speedup comes from — one fused
draft+verify program emits K+1 tokens per engine step instead of K+1
single-token steps (per-step dispatch/host overhead is what serving
decode pays per token; a real small draft adds a flops win on top at
whatever accept rate it earns). ``"draft": "random"`` swaps in a small
random-init draft for the accept≈0 floor.

Usage: python sharded_probe.py --one '{"model": "micro", "k": 8}'
Prints one line: RESULT {json}

CPU-sized like serve_probe: runs without a TPU every bench round.
"""

import dataclasses
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _model_cfg(name):
    import jax.numpy as jnp

    from ray_tpu.models import MODEL_REGISTRY
    from ray_tpu.models.transformer import TransformerConfig
    if name == "micro":
        # per-step-overhead-bound on the CI CPU: each decode step's cost
        # is dominated by dispatch + host sync rather than matmul flops
        # — the CPU stand-in for TPU decode's memory-bound regime, where
        # a (K+1)-wide verify costs ~one step and speculation pays. The
        # compute-bound "tiny" shape deliberately shows the other side
        # (vs_no_spec < 1 when flops dominate and the draft isn't
        # cheaper than the target).
        return TransformerConfig(
            vocab_size=256, d_model=64, n_layers=1, n_heads=2,
            n_kv_heads=1, d_ff=256, max_seq_len=512, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False)
    if name == "tiny":
        return TransformerConfig(
            vocab_size=256, d_model=256, n_layers=6, n_heads=8,
            n_kv_heads=4, d_ff=1024, max_seq_len=512, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False)
    cfg = MODEL_REGISTRY[name]
    return dataclasses.replace(cfg, param_dtype=jnp.bfloat16,
                               dtype=jnp.bfloat16, remat=False)


def _draft_cfg(tc):
    """A ~1/8-cost draft shape for the random-draft floor."""
    return dataclasses.replace(
        tc, d_model=max(32, tc.d_model // 4),
        n_layers=max(1, tc.n_layers // 3),
        n_heads=max(1, tc.n_heads // 4),
        n_kv_heads=max(1, tc.n_kv_heads // 4),
        d_ff=max(64, tc.d_ff // 4))


def _requests(spec, rng):
    n = spec.get("n_requests", 8)
    plo, phi = spec.get("prompt_lens", [4, 24])
    nlo, nhi = spec.get("new_tokens", [24, 48])
    vocab = spec.get("vocab", 128)
    return [{"prompt": rng.integers(0, vocab, size=int(
                 rng.integers(plo, phi + 1))).astype("int32").tolist(),
             "new": int(rng.integers(nlo, nhi + 1))}
            for _ in range(n)]


def _serve_all(replica, reqs):
    """Serial lockstep serving (the gang admits one SPMD stream at a
    time); returns tokens/s over the whole set."""
    t0 = time.perf_counter()
    total = 0
    for r in reqs:
        total += len(replica.generate(r["prompt"],
                                      max_new_tokens=r["new"]))
    return total / (time.perf_counter() - t0)


def run(spec):
    import jax
    import numpy as np

    from ray_tpu.models import TransformerLM
    from ray_tpu.serve.sharded import ShardedEngineReplica

    tc = _model_cfg(spec.get("model", "micro"))
    spec.setdefault("vocab", min(tc.vocab_size, 128))
    model = TransformerLM(tc)
    n_slots = spec.get("n_slots", 4)
    max_len = spec.get("max_len", min(256, tc.max_seq_len))
    k = int(spec.get("k", 8))
    kv_quant = spec.get("kv_quant", "none")
    n_devices = len(jax.devices())
    rng = np.random.default_rng(spec.get("seed", 0))
    reqs = _requests(spec, rng)

    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    common = dict(n_slots=n_slots, max_len=max_len,
                  prefill_chunk=spec.get("prefill_chunk", 16),
                  prefill_budget=spec.get("prefill_budget", 32),
                  prefix_cache_slots=spec.get("prefix_cache_slots", 2),
                  params_fn=lambda: params, seed=0)
    if spec.get("draft") == "random":
        sd = {"draft_model": _draft_cfg(tc), "k": k}
    else:
        sd = {"draft_model": tc, "k": k,
              "draft_params_fn": lambda: params}

    rep = ShardedEngineReplica(model, spec_decode=sd, kv_quant=kv_quant,
                               **common)
    base = ShardedEngineReplica(model, kv_quant=kv_quant, **common)
    # warmup: compile every program on both replicas
    rep.generate(reqs[0]["prompt"][:4], max_new_tokens=2)
    base.generate(reqs[0]["prompt"][:4], max_new_tokens=2)

    runs = spec.get("runs", 3)
    spec_rates = sorted(_serve_all(rep, reqs) for _ in range(runs))
    base_rates = sorted(_serve_all(base, reqs) for _ in range(runs))
    med = spec_rates[len(spec_rates) // 2]
    base_med = base_rates[len(base_rates) // 2]
    st = rep.stats()

    # greedy parity: the artifact carries its own exactness evidence
    out_s = rep.generate(reqs[0]["prompt"], max_new_tokens=16)
    out_b = base.generate(reqs[0]["prompt"], max_new_tokens=16)

    result = {
        "model": spec.get("model", "micro"), "n_slots": n_slots,
        "max_len": max_len, "k": k, "kv_quant": kv_quant,
        "draft": spec.get("draft", "self"),
        "n_requests": len(reqs), "n_devices": n_devices,
        "gang_world": st["gang_world"],
        "sharded_decode_tokens_per_s": round(med, 1),
        "tokens_per_s_per_chip": round(med / n_devices, 1),
        "spread": round((spec_rates[-1] - spec_rates[0]) / med, 3)
        if med else 0.0,
        "runs": [round(r, 1) for r in spec_rates],
        "no_spec_tokens_per_s": round(base_med, 1),
        "vs_no_spec": round(med / base_med, 3) if base_med else None,
        "spec_decode_accept_rate": st["spec_accept_rate"],
        "spec_tokens_proposed": st["spec_tokens_proposed"],
        "spec_tokens_accepted": st["spec_tokens_accepted"],
        "decode_compile_count": st["decode_compile_count"],
        "spec_verify_compile_count": st["spec_verify_compile_count"],
        "greedy_parity": out_s == out_b,
    }
    if kv_quant == "int8":
        result["kv_quant_slot_gain_vs_fp16"] = st[
            "kv_quant_slot_gain_vs_fp16"]
    return result


if __name__ == "__main__":
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()   # before the first compile; children inherit
    spec = json.loads(sys.argv[sys.argv.index("--one") + 1])
    print("RESULT " + json.dumps(run(spec)), flush=True)
