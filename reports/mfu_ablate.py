"""Train-step MFU ablation on the TPU chip this process holds.

Grid: model size x attention impl x remat policy x batch x seq len
(+ head-dim variants: 8 heads of 128 lanes vs 16 of 64). Each config runs
in a subprocess so an OOM/compile failure can't kill the sweep; results
append to reports/mfu_ablation.jsonl and the winner feeds the flagship
bench config (VERDICT r2 item 1: ablate and push the MFU headline).

Usage:
  python reports/mfu_ablate.py            # run the grid (skips done rows)
  python reports/mfu_ablate.py --one '{"model": "llama-350m", ...}'
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

GRID = [
    # baseline (round-2 headline shape)
    {"model": "llama-125m", "B": 16, "L": 1024, "attn": "reference",
     "remat_policy": "dots"},
    {"model": "llama-125m", "B": 16, "L": 1024, "attn": "flash",
     "remat_policy": "dots"},
    # 350m: bigger matmuls; OOMed with reference attention at r2
    {"model": "llama-350m", "B": 16, "L": 1024, "attn": "flash",
     "remat_policy": "dots"},
    {"model": "llama-350m", "B": 8, "L": 1024, "attn": "flash",
     "remat_policy": "dots"},
    {"model": "llama-350m", "B": 32, "L": 1024, "attn": "flash",
     "remat_policy": "dots"},
    {"model": "llama-350m", "B": 16, "L": 1024, "attn": "flash",
     "remat_policy": "nothing"},
    {"model": "llama-350m", "B": 16, "L": 1024, "attn": "flash",
     "remat_policy": "dots_no_batch"},
    {"model": "llama-350m", "B": 16, "L": 2048, "attn": "flash",
     "remat_policy": "dots"},
    {"model": "llama-350m", "B": 8, "L": 2048, "attn": "flash",
     "remat_policy": "dots"},
    # head_dim 128 variants (full-lane MXU tiles, no pad waste)
    {"model": "llama-350m", "B": 16, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "n_heads": 8, "n_kv_heads": 8},
    {"model": "llama-350m", "B": 16, "L": 2048, "attn": "flash",
     "remat_policy": "dots", "n_heads": 8, "n_kv_heads": 8},
    {"model": "llama-125m", "B": 16, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "n_heads": 6, "n_kv_heads": 6},
    # 1b ladder rung (d_model=2048): does it fit, and at what MFU?
    {"model": "llama-1b", "B": 8, "L": 1024, "attn": "flash",
     "remat_policy": "dots"},
    {"model": "llama-1b", "B": 4, "L": 2048, "attn": "flash",
     "remat_policy": "dots"},
    {"model": "llama-1b", "B": 8, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "n_heads": 16, "n_kv_heads": 16},
    # wave 2: push the h=128-lane winner harder
    {"model": "llama-350m", "B": 24, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "n_heads": 8, "n_kv_heads": 8},
    {"model": "llama-350m", "B": 32, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "n_heads": 8, "n_kv_heads": 8},
    {"model": "llama-350m", "B": 8, "L": 2048, "attn": "flash",
     "remat_policy": "dots", "n_heads": 8, "n_kv_heads": 8},
    # 1b with a factored optimizer (fp32 adam state alone is 13.2G)
    {"model": "llama-1b", "B": 8, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "n_heads": 16, "n_kv_heads": 16,
     "opt": "adafactor"},
    {"model": "llama-1b", "B": 16, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "n_heads": 16, "n_kv_heads": 16,
     "opt": "adafactor"},
    {"model": "llama-1b", "B": 8, "L": 1024, "attn": "flash",
     "remat_policy": "nothing", "n_heads": 16, "n_kv_heads": 16,
     "opt": "adafactor"},
    # wave 3 (round 4): chunked cross-entropy kills the [B,L,32000]
    # logits buffer — does it unlock tpu-1b B=16 / the tpu-3b rung?
    {"model": "tpu-1b", "B": 8, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "opt": "adafactor", "loss_chunk": 256},
    {"model": "tpu-1b", "B": 16, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "opt": "adafactor", "loss_chunk": 256},
    # tpu-3b: largest-single-chip attempt — bf16 params + adafactor +
    # chunked CE; `dots` likely OOMs on saved activations at d=3072
    {"model": "tpu-3b", "B": 8, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "opt": "adafactor", "loss_chunk": 256,
     "param_dtype": "bf16"},
    {"model": "tpu-3b", "B": 8, "L": 1024, "attn": "flash",
     "remat_policy": "nothing", "opt": "adafactor", "loss_chunk": 256,
     "param_dtype": "bf16"},
    {"model": "tpu-3b", "B": 4, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "opt": "adafactor", "loss_chunk": 256,
     "param_dtype": "bf16"},
    {"model": "tpu-3b", "B": 16, "L": 1024, "attn": "flash",
     "remat_policy": "nothing", "opt": "adafactor", "loss_chunk": 256,
     "param_dtype": "bf16"},
    {"model": "tpu-3b", "B": 8, "L": 2048, "attn": "flash",
     "remat_policy": "nothing", "opt": "adafactor", "loss_chunk": 256,
     "param_dtype": "bf16"},
    {"model": "tpu-3b", "B": 4, "L": 2048, "attn": "flash",
     "remat_policy": "dots", "opt": "adafactor", "loss_chunk": 256,
     "param_dtype": "bf16"},
    {"model": "tpu-7b", "B": 4, "L": 1024, "attn": "flash",
     "remat_policy": "nothing", "opt": "adafactor", "loss_chunk": 256,
     "param_dtype": "bf16"},
    # wave 4: probe the dots-activation boundary around the 3b winner
    {"model": "tpu-3b", "B": 6, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "opt": "adafactor", "loss_chunk": 128,
     "param_dtype": "bf16"},
    {"model": "tpu-3b", "B": 4, "L": 1536, "attn": "flash",
     "remat_policy": "dots", "opt": "adafactor", "loss_chunk": 128,
     "param_dtype": "bf16"},
    {"model": "tpu-1b", "B": 12, "L": 1024, "attn": "flash",
     "remat_policy": "dots", "opt": "adafactor", "loss_chunk": 128},
]

OUT = os.path.join(os.path.dirname(__file__), "mfu_ablation.jsonl")


def train_step_flops(cfg, B: int, L: int) -> float:
    """Useful (non-remat) fwd+bwd FLOPs per step; same formula as bench.py
    so ablation numbers and the headline are comparable."""
    n_layer = cfg.n_layers * (
        cfg.d_model * (cfg.n_heads * cfg.head_dim) * 2      # q, o proj
        + cfg.d_model * (cfg.n_kv_heads * cfg.head_dim) * 2  # k, v proj
        + 3 * cfg.d_model * cfg.d_ff)
    n_unembed = cfg.d_model * cfg.vocab_size
    attn = cfg.n_layers * 4 * B * L * L * (cfg.n_heads * cfg.head_dim) * 3 / 2
    return 6 * (n_layer + n_unembed) * B * L + attn


def run_one(spec: dict) -> dict:
    import jax
    import optax

    from ray_tpu.models import MODEL_REGISTRY, TransformerLM
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_fns

    import jax.numpy as jnp

    cfg = MODEL_REGISTRY[spec["model"]]
    overrides = {k: spec[k] for k in
                 ("n_heads", "n_kv_heads", "d_ff", "d_model") if k in spec}
    if spec.get("param_dtype") == "bf16":
        # pure-bf16 training: halves params+grads HBM (the 3b rung's only
        # way onto one 16 GB chip); master-weight fp32 remains the
        # default for every smaller config
        overrides["param_dtype"] = jnp.bfloat16
    cfg = dataclasses.replace(
        cfg, attention_impl=spec.get("attn", "auto"),
        remat_policy=spec.get("remat_policy", "dots"),
        remat=spec.get("remat_policy") != "none", **overrides)
    B, L = spec["B"], spec["L"]
    model = TransformerLM(cfg)
    mesh = make_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    opt = (optax.adafactor(3e-4) if spec.get("opt") == "adafactor"
           else optax.adamw(3e-4))
    init_fn, step_fn, _ = make_train_fns(
        model, opt, mesh, batch_shape=(B, L + 1),
        loss_chunk=spec.get("loss_chunk"))
    t_compile = time.perf_counter()
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, L + 1), 0,
                                cfg.vocab_size)
    for _ in range(3):
        state, m = step_fn(state, tokens)
    float(m["loss"])
    t_compile = time.perf_counter() - t_compile
    steps = 10
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step_fn(state, tokens)
    float(m["loss"])
    dt = (time.perf_counter() - t0) / steps
    from ray_tpu.util.profiling import detect_peak_flops
    mfu = train_step_flops(cfg, B, L) / dt / detect_peak_flops()
    return {**spec, "ms_per_step": round(dt * 1e3, 2),
            "tokens_per_s": round(B * L / dt, 1),
            "mfu": round(mfu, 4), "compile_s": round(t_compile, 1),
            "loss": round(float(m["loss"]), 3)}


def main():
    if "--one" in sys.argv:
        spec = json.loads(sys.argv[sys.argv.index("--one") + 1])
        print("RESULT " + json.dumps(run_one(spec)), flush=True)
        return

    done = set()
    if os.path.exists(OUT):
        with open(OUT) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("error") == "timeout":
                        continue    # only timeouts retry on rerun
                    done.add(json.dumps(
                        {k: r[k] for k in sorted(r)
                         if k in ("model", "B", "L", "attn", "remat_policy",
                                  "n_heads", "n_kv_heads", "opt",
                                  "loss_chunk", "param_dtype")},
                        sort_keys=True))
                except json.JSONDecodeError:
                    pass
    for spec in GRID:
        key = json.dumps({k: v for k, v in sorted(spec.items())},
                         sort_keys=True)
        if key in done:
            print(f"skip (done): {spec}", file=sys.stderr)
            continue
        print(f"running: {spec}", file=sys.stderr, flush=True)
        try:
            out = subprocess.run(
                [sys.executable, __file__, "--one", json.dumps(spec)],
                capture_output=True, text=True, timeout=900,
                env={**os.environ, "PYTHONPATH": os.pathsep.join(
                    p for p in (os.environ.get("PYTHONPATH"), _REPO) if p)})
        except subprocess.TimeoutExpired:
            row = {**spec, "error": "timeout"}
        else:
            row = None
            for line in (out.stdout or "").splitlines():
                if line.startswith("RESULT "):
                    row = json.loads(line[7:])
            if row is None:
                tail = (out.stderr or "")[-2000:]
                err = "OOM" if "hbm" in tail.lower() else "failed"
                row = {**spec, "error": err, "detail": tail[-300:]}
        with open(OUT, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()   # before the first compile; children inherit
    main()
