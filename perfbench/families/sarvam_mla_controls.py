"""The controls of `correct` in the `sarvam_mla` family's cells: the faults
ISSUE 50 names, planted in the served path at the configuration's own sizes,
each judged as a run of the cell is judged.

What a run's `correct` rests on, and how a reading is made, is said in
families/keye_vl2_controls.py, whose `reference_prompts` and `four_bits`
this file uses, in families/minicpm_sala_controls.py, whose `serve` (slots
that have had an owner, the cases in flight together) it uses, and in
families/afmoe_controls.py, whose `judge` it uses: the engine is driven
directly, the reference scores against the SOUND weights, and the family's
two numbers beside the count of tokens (families/sarvam_mla.py `scored`)
are taken with the fault planted (`program_rows`, on the served tokens).

    python3 perfbench/families/sarvam_mla_controls.py \
        --workload sarvam-105b.longdoc-answer --seeds 11 12 \
        --controls sound bias_weighs --out chiprun_out/controls.jsonl
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# name -> what is planted
CONTROLS = {
    "sound": "nothing",
    "matmuls_below_bf16": "every matmul weight rounded to 4 significant "
                          "bits (a float8's; bf16 keeps 8)",
    "latent_pool_three_bits": "every latent row, as attended and as "
                              "cached, rounded to 3 mantissa bits",
    "plain_rotary": "rope's frequencies as they are, not YaRN's blend",
    "scale_without_m2": "the softmax's scale 192^-1/2, YaRN's m^2 left out",
    "latent_norm_skipped": "the latent cached as projected, not normed",
    "row_rope_key_unrotated": "a decode row's own rope key is cached and "
                              "attended unrotated",
    "shared_expert_dropped": "a token passes its routed experts alone",
    "bias_weighs": "the taken experts are weighed by score + bias",
}
WARM = (3000, 8)     # the slots' earlier owners: prompt, generated tokens
_ROUNDED = ("kernel", "gate", "up", "down", "kv_up")


@contextlib.contextmanager
def planted(name: str, model, params, consume: bool = False):
    """-> (model, params) as served with the control `name` planted; the
    program's functions are the sound ones again on leaving. `consume`:
    a control that changes the weights may take `params`' own buffers
    (two copies of the served weights do not fit the chip)."""
    import jax
    import jax.numpy as jnp

    from perfbench.families.keye_vl2_controls import four_bits
    from ray_tpu.models import (TransformerLM, latent_attention as la, moe,
                                transformer as tr)
    sound = (la.latent_rows, la.softmax_scale, tr.yarn_blend,
             moe.sigmoid_route)
    rows_of, route = la.latent_rows, moe.sigmoid_route

    def with_cfg(**over):
        return TransformerLM(dataclasses.replace(model.cfg, **over))

    try:
        if name == "matmuls_below_bf16":
            rounded = jax.jit(four_bits,
                              donate_argnums=(0,) if consume else ())
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: rounded(a)
                if path[-1].key in _ROUNDED else a, params)
        elif name == "latent_pool_three_bits":
            la.latent_rows = lambda *a: jax.lax.reduce_precision(
                rows_of(*a), exponent_bits=8, mantissa_bits=3)
        elif name == "plain_rotary":
            tr.yarn_blend = lambda d, theta, yarn: jnp.ones((d // 2,),
                                                            jnp.float32)
        elif name == "scale_without_m2":
            la.softmax_scale = lambda cfg: cfg.head_dim ** -0.5
        elif name == "latent_norm_skipped":
            la.latent_rows = lambda ckr, norm, positions, cfg: rows_of(
                ckr, lambda c: c, positions, cfg)
        elif name == "row_rope_key_unrotated":
            # (one row a slot: the rows of `_split_rows`, or a decode step's)
            la.latent_rows = lambda ckr, norm, positions, cfg: rows_of(
                ckr, norm, positions * (ckr.shape[1] != 1), cfg)
        elif name == "shared_expert_dropped":
            model = with_cfg(n_shared_experts=0)
        elif name == "bias_weighs":
            def weighed(x, router, bias, k):
                scores, _, taken = route(x, router, bias, k)
                return scores, jnp.take_along_axis(
                    scores + bias, taken, axis=-1), taken
            moe.sigmoid_route = weighed
        elif name != "sound":
            raise KeyError(f"{name!r} is none of {list(CONTROLS)}")
        yield model, params
    finally:
        (la.latent_rows, la.softmax_scale, tr.yarn_blend,
         moe.sigmoid_route) = sound


def readings(cfg: dict, mix: dict, seed: int, controls):
    """One row a control at this seed."""
    from perfbench import spec, weights
    from perfbench.families.afmoe_controls import judge
    from perfbench.families.keye_vl2_controls import reference_prompts
    from perfbench.families.minicpm_sala_controls import serve
    family = spec.family_of(cfg)
    model = family.build_model(family.model_kwargs(cfg))
    params = weights.seeded_params(model, seed, family.weight_rule)
    cases = reference_prompts(mix, cfg, seed)
    for name in controls:
        t0 = time.monotonic()
        family._programs.cache_clear()    # a planted function is traced anew
        try:
            with planted(name, model, params, consume=True) as (
                    m, served_params):
                served = serve(m, served_params, cfg, cases, seed, WARM)
                rows = [family.program_rows(served_params, cfg, p, g, model=m)
                        for (p, _), g in zip(cases, served)]
                del served_params
        finally:
            family._programs.cache_clear()
        if name == "matmuls_below_bf16":
            # `params` were consumed: nothing of the old tree stays on the
            # device while the sound weights are drawn anew
            import jax
            rows = jax.device_get(rows)
            params = None
            gc.collect()
            params = weights.seeded_params(model, seed, family.weight_rule)
        gc.collect()
        row = judge(cfg, params, cases, served, rows)
        del rows
        yield dict(row, control=name, seed=seed,
                   seconds=round(time.monotonic() - t0, 1))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", default=list(CONTROLS),
                    choices=list(CONTROLS))
    ap.add_argument("--out", default=None, help="rows, gaps and all, as "
                    "JSON lines (the printed rows leave the gaps out)")
    ap.add_argument("--budget-s", type=float, default=float("inf"),
                    help="start no further seed after this many seconds")
    args = ap.parse_args(argv)

    from perfbench import spec
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(bench, cell["traffic"])
    t_start, bad = time.monotonic(), 0
    for seed in args.seeds:
        if time.monotonic() - t_start > args.budget_s:
            print(f"CONTROLS budget spent before seed {seed}", flush=True)
            break
        for row in readings(cfg, mix, seed, args.controls):
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            bad += row["passes"] != (row["control"] == "sound")
            print("CONTROL " + json.dumps(
                {k: v for k, v in row.items()
                 if k not in ("gaps", "logit_rms_each")}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
