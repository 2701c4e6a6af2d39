"""The controls of `correct` in the `falcon_h1` family's cells: the faults
ISSUE 44 names, planted in the served path at the configuration's own
sizes, each judged as a run of the cell is judged.

What a run's `correct` rests on, and how a reading is made, is said in
families/keye_vl2_controls.py, whose `reference_prompts` and `four_bits`
this file uses, and in families/minicpm_sala_controls.py, whose `serve`
(slots that have had an owner, the cases in flight together) it uses: the
engine is driven directly, the reference scores against the SOUND weights.
This family's comparison has four numbers beside the count of tokens
(families/falcon_h1.py `scored`): all are taken with the fault planted
(`program_rows`, on the served tokens), and `judge` folds them as a run of
the cell does. A rounding is planted with `lax.reduce_precision`, which XLA
does not drop.

    python3 perfbench/families/falcon_h1_controls.py \
        --workload falcon-h1-34b.rag-answer --seeds 11 12 13 \
        --controls sound state_in_bf16 --out chiprun_out/controls.jsonl
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
    "state_in_bf16": "the mixer's state is kept in bf16 between calls",
    "state_zeroed_at_tile_start": "every prefill tile's recurrence starts "
                                  "from a zero state",
    "tail_zeroed_at_tile_start": "every prefill tile's convolution starts "
                                 "from a zero tail",
    "tail_from_padded_rows": "the convolution's tail is the tile's last "
                             "rows, padded or not",
    "state_of_last_owner_left": "a slot keeps its last owner's state and "
                                "tail when a new request takes it",
    "ssm_branch_left_out": "a block is its attention heads and MLP alone",
    "attention_branch_left_out": "a block is its mixer and MLP alone",
    "key_multiplier_at_1": "the keys are not scaled",
    "ssm_out_multiplier_at_1": "the mixer's output is not scaled",
    "groups_swapped": "a head reads B and C of the other group",
    "dt_without_softplus": "dt + dt_bias enters the recurrence as it is "
                           "(its magnitude, so that no decay grows)",
}
WARM = (600, 8)      # the slots' earlier owners: prompt, generated tokens


@contextlib.contextmanager
def planted(name: str, model, params, consume: bool = False):
    """-> (model, params) as served with the control `name` planted; the
    program's functions are the sound ones again on leaving. `consume`:
    a control that changes the weights may take `params`' own buffers
    (two copies of the served weights do not fit the chip)."""
    import jax
    import jax.numpy as jnp

    from perfbench.families.keye_vl2_controls import four_bits
    from ray_tpu.inference import kv_cache
    from ray_tpu.models import TransformerLM, ssm
    sound = (ssm.ssd_scan, ssm.ssd_step, ssm.causal_conv,
             kv_cache.SlotPool.insert, jax.nn.softplus)
    scan, step, conv, insert, _ = sound

    def with_cfg(**over):
        return TransformerLM(dataclasses.replace(model.cfg, **over))

    try:
        if name == "matmuls_below_bf16":
            rounded = jax.jit(four_bits,
                              donate_argnums=(0,) if consume else ())
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: rounded(a)
                if path[-1].key == "kernel" else a, params)
        elif name == "state_in_bf16":
            def coarse(s):
                return jax.lax.reduce_precision(s, exponent_bits=8,
                                                mantissa_bits=7)

            def kept(fn):
                def run(x, dt, A, B, C, D, state, *a, **kw):
                    y, new = fn(x, dt, A, B, C, D, coarse(state), *a, **kw)
                    return y, coarse(new)
                return run
            ssm.ssd_scan, ssm.ssd_step = kept(scan), kept(step)
        elif name == "state_zeroed_at_tile_start":
            ssm.ssd_scan = lambda x, dt, A, B, C, D, state, *a, **kw: scan(
                x, dt, A, B, C, D, jnp.zeros_like(state), *a, **kw)
        elif name == "tail_zeroed_at_tile_start":
            ssm.causal_conv = lambda x, tail, *a, **kw: conv(
                x, tail if x.shape[1] == 1 else jnp.zeros_like(tail),
                *a, **kw)
        elif name == "tail_from_padded_rows":
            def padded(x, tail, w, b, real=None):
                y, _ = conv(x, tail, w, b, real)
                if x.shape[1] == 1:          # a decode row: as it was
                    return conv(x, tail, w, b, real)
                return y, conv(x, tail, w, b, None)[1]
            ssm.causal_conv = padded
        elif name == "state_of_last_owner_left":
            def left(pool, scratch, slot):
                scratch = list(scratch)
                for n in ("s", "c"):
                    # (a copy: of a pool of one slot the slice is the pool,
                    # which the insert donates)
                    scratch[list(pool.shapes).index(n)] = jnp.copy(
                        getattr(pool, n)[:, slot:slot + 1])
                return insert(pool, scratch, slot)
            kv_cache.SlotPool.insert = left
        elif name == "ssm_branch_left_out":
            model = with_cfg(ssm_out_mult=0.0)
        elif name == "attention_branch_left_out":
            model = with_cfg(attn_out_mult=0.0)
        elif name == "key_multiplier_at_1":
            model = with_cfg(key_mult=1.0)
        elif name == "ssm_out_multiplier_at_1":
            model = with_cfg(ssm_out_mult=1.0)
        elif name == "groups_swapped":
            def swapped(fn):
                return lambda x, dt, A, B, C, *a, **kw: fn(
                    x, dt, A, B[:, :, ::-1], C[:, :, ::-1], *a, **kw)
            ssm.ssd_scan, ssm.ssd_step = swapped(scan), swapped(step)
        elif name == "dt_without_softplus":
            jax.nn.softplus = jnp.abs
        elif name != "sound":
            raise KeyError(f"{name!r} is none of {list(CONTROLS)}")
        yield model, params
    finally:
        (ssm.ssd_scan, ssm.ssd_step, ssm.causal_conv,
         kv_cache.SlotPool.insert, jax.nn.softplus) = sound


def judge(cfg: dict, params, cases, served, rows) -> dict:
    """serve_cell's reading of what was served: replica.bench_reference's
    padding, the family's gaps (with `rows`, what `program_rows` gave a
    case each, taken while the fault was planted), the share within the
    configuration's `logit_gap` and whether it reaches `share_within`;
    beside it each number alone."""
    from perfbench import spec
    family = spec.family_of(cfg)
    tol = cfg["reference_tolerance"]
    pad = max(len(p) + len(g) for (p, _), g in zip(cases, served))
    pad = -(-pad // 128) * 128
    scores = [family.scored(params, cfg, p, g, pad, r)
              for (p, _), g, r in zip(cases, served, rows)]
    gaps = [family.folded(sc, tol) for sc in scores]
    flat = [x for g in gaps for x in g]
    share = sum(x <= tol["logit_gap"] for x in flat) / len(flat)
    return {"n_tokens": len(flat), "logit_gap": tol["logit_gap"],
            "share_within_gap": share, "beyond": sum(
                x > tol["logit_gap"] for x in flat),
            "passes": share >= tol["share_within"], "max_gap": max(flat),
            "tokens_beyond_by_case": [sum(
                x > tol["logit_gap"] for x in sc["gaps"]) for sc in scores],
            "logit_rms_by_case": [sc["logit_rms"] for sc in scores],
            "edge_rms_by_case": [sc["edge_rms"] for sc in scores],
            "logit_rms_limit": tol["logit_rms"],
            "state_rel_by_case": [family.state_number(sc["state_rel"])
                                  for sc in scores],
            "state_rel_limit": tol["state_rel"],
            "tail_rel_by_case": [sc["tail_rel"] for sc in scores],
            "tail_rel_limit": tol["tail_rel"],
            "spread": [sc["spread"] for sc in scores],
            "gaps": gaps,
            "logit_rms_each": [sc["logit_rms_each"] for sc in scores],
            "state_rel": [sc["state_rel"] for sc in scores]}


def readings(cfg: dict, mix: dict, seed: int, controls):
    """One row a control at this seed."""
    from perfbench import spec, weights
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
                served = serve(m, served_params, cfg, cases, seed)
                rows = [family.program_rows(served_params, cfg, p, g, model=m)
                        for (p, _), g in zip(cases, served)]
                del served_params
        finally:
            family._programs.cache_clear()
        if name == "matmuls_below_bf16":
            # `params` were consumed, but for the tables (5.3 GB, which the
            # control shares): nothing of the old tree or of the program's
            # rows stays on the device while the sound weights are drawn
            # anew (the draw's peak is 15.5 GB of the chip's 16.9)
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
                 if k not in ("gaps", "logit_rms_each", "state_rel")}),
                flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
