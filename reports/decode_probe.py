"""Single-chip decode-throughput probe (bench.py subprocess; the
serving-side counterpart of mfu_ablate.py): prefill a prompt, then
lax.scan single-token KV-cache decode steps, report tokens/s.

Usage: python decode_probe.py --one '{"model": "tpu-1b", "B": 8,
                                      "prompt": 128, "new": 64}'
Prints one line: RESULT {json}
"""

import dataclasses
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def run(spec):
    import jax
    import numpy as np
    import jax.numpy as jnp

    from ray_tpu.models import MODEL_REGISTRY, TransformerLM
    from ray_tpu.models.generate import make_generate_fn
    from ray_tpu.parallel import MeshConfig, make_mesh

    cfg = MODEL_REGISTRY[spec["model"]]
    # bf16 params: inference wants the half-width weights (and the 3B
    # rung only fits one 16 GB chip that way)
    cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16,
                              dtype=jnp.bfloat16, remat=False)
    model = TransformerLM(cfg)
    B = spec.get("B", 8)
    prompt_len = spec.get("prompt", 128)
    new = spec.get("new", 64)
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, seq=1, tensor=1),
                     devices=jax.devices()[:1])
    # two generate programs differing ONLY in decode-step count: the
    # DIFFERENCE of their wall times isolates the per-token decode rate
    # from the shared prefill cost and the fixed per-call overhead
    short = max(4, new // 4)
    init_fn, gen_long, _ = make_generate_fn(model, mesh, batch=B,
                                            prompt_len=prompt_len,
                                            max_new_tokens=new)
    _, gen_short, _ = make_generate_fn(model, mesh, batch=B,
                                       prompt_len=prompt_len,
                                       max_new_tokens=short)
    params = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, prompt_len),
                                0, cfg.vocab_size)

    def timed(fn, key):
        # np.asarray forces the full device->host materialization;
        # fresh keys per call so no layer can serve a cached result
        t0 = time.perf_counter()
        np.asarray(fn(params, tokens, key))
        return time.perf_counter() - t0

    out = np.asarray(gen_long(params, tokens, jax.random.PRNGKey(2)))
    assert out.shape == (B, new)
    np.asarray(gen_short(params, tokens, jax.random.PRNGKey(3)))

    # bench integrity: each decode step streams the full weight set from
    # HBM once, so tokens/s is bounded by B * HBM_BW / param_bytes. A
    # sample whose long-minus-short delta is ~0 (the 384e9 tok/s
    # artifact: both programs served by a caching layer) or whose rate
    # beats the roofline with 2x slack is physically impossible —
    # reject it and resample instead of publishing it.
    param_bytes = sum(np.asarray(x).size * np.asarray(x).dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    from ray_tpu.util.profiling import detect_peak_bytes_per_s
    hbm_bw = detect_peak_bytes_per_s()    # the table's, by device kind
    roofline = 2.0 * B * hbm_bw / max(1, param_bytes)
    min_delta = 1e-3          # below timer noise = not a real measurement
    rates, e2e, rejected = [], [], 0
    attempt = 0
    while len(rates) < 3 and attempt < 10:
        dt_long = timed(gen_long, jax.random.PRNGKey(10 + attempt))
        dt_short = timed(gen_short, jax.random.PRNGKey(20 + attempt))
        attempt += 1
        delta = dt_long - dt_short
        rate = B * (new - short) / max(1e-9, delta)
        if delta < min_delta or rate > roofline:
            rejected += 1
            continue
        rates.append(rate)
        e2e.append(B * new / dt_long)
    if not rates:
        raise RuntimeError(
            f"decode probe produced no physically plausible sample in "
            f"{attempt} attempts ({rejected} rejected; roofline "
            f"{roofline:.3e} tok/s)")
    rates.sort()
    e2e.sort()
    med = rates[len(rates) // 2]
    spread = (rates[-1] - rates[0]) / med if med else 0.0
    return {"model": spec["model"], "B": B, "prompt": prompt_len,
            "new": new, "decode_tokens_per_s": round(med, 1),
            "e2e_tokens_per_s": round(e2e[len(e2e) // 2], 1),
            "spread": round(spread, 3), "rejected_samples": rejected,
            "roofline_tokens_per_s": round(roofline, 1),
            "runs": [round(r, 1) for r in rates]}


if __name__ == "__main__":
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()   # before the first compile; children inherit
    spec = json.loads(sys.argv[sys.argv.index("--one") + 1])
    print("RESULT " + json.dumps(run(spec)), flush=True)
