"""The controls of `correct` in the `afmoe` family's cells: the faults
ISSUE 46 names, planted in the served path at the configuration's own
sizes, each judged as a run of the cell is judged.

What a run's `correct` rests on, and how a reading is made, is said in
families/keye_vl2_controls.py, whose `reference_prompts` and `four_bits`
this file uses, and in families/minicpm_sala_controls.py, whose `serve`
(slots that have had an owner, the cases in flight together) it uses: the
engine is driven directly, the reference scores against the SOUND weights.
This family's comparison has two numbers beside the count of tokens
(families/afmoe.py `scored`): both are taken with the fault planted
(`program_rows`, on the served tokens), and `judge` folds both as a run of
the cell does.

    python3 perfbench/families/afmoe_controls.py \
        --workload trinity-large-preview.longdoc-report --seeds 11 12 \
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
    "window_an_eighth_short": "a sliding layer attends the 3,584 newest "
                              "positions, not 4,096: the window's edge",
    "ring_not_wrapped": "a tile past the ring's end is written on its last "
                        "places, not at its positions modulo the ring",
    "rotary_on_full_layers": "the full-attention layers rotate q and k too",
    "bias_weighs": "the taken experts are weighed by score + bias",
    "shared_expert_dropped": "a token passes its routed experts alone",
}
WARM = (6000, 8)     # the slots' earlier owners: prompt, generated tokens


@contextlib.contextmanager
def planted(name: str, model, params, consume: bool = False):
    """-> (model, params) as served with the control `name` planted; the
    program's functions are the sound ones again on leaving. `consume`:
    a control that changes the weights may take `params`' own buffers
    (two copies of the served weights do not fit the chip)."""
    import jax
    import jax.numpy as jnp

    from perfbench.families.keye_vl2_controls import four_bits
    from ray_tpu.models import TransformerLM, moe, transformer as tr
    sound = (tr._ring_write, moe.sigmoid_route)
    route = moe.sigmoid_route

    def with_cfg(**over):
        return TransformerLM(dataclasses.replace(model.cfg, **over))

    try:
        if name == "matmuls_below_bf16":
            rounded = jax.jit(four_bits,
                              donate_argnums=(0,) if consume else ())
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: rounded(a)
                if path[-1].key in ("kernel", "gate", "up", "down") else a,
                params)
        elif name == "window_an_eighth_short":
            model = with_cfg(window=model.cfg.window * 7 // 8)
        elif name == "ring_not_wrapped":
            def clamped(ring, new, pos0, pos_axis: int = -3):
                room = ring.shape[pos_axis] - new.shape[pos_axis]
                return tr._cache_write(ring, new, jnp.minimum(pos0, room),
                                       pos_axis)
            tr._ring_write = clamped
        elif name == "rotary_on_full_layers":
            model = with_cfg(attn_rope=True)
        elif name == "bias_weighs":
            def weighed(x, router, bias, k):
                scores, _, taken = route(x, router, bias, k)
                return scores, jnp.take_along_axis(
                    scores + bias, taken, axis=-1), taken
            moe.sigmoid_route = weighed
        elif name == "shared_expert_dropped":
            model = with_cfg(n_shared_experts=0)
        elif name != "sound":
            raise KeyError(f"{name!r} is none of {list(CONTROLS)}")
        yield model, params
    finally:
        tr._ring_write, moe.sigmoid_route = sound


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
            "logit_rms_limit": tol["logit_rms"],
            "route_rel_by_case": [sc["route_rel"] for sc in scores],
            "route_rel_limit": tol["route_rel"],
            "spread": [sc["spread"] for sc in scores],
            "gaps": gaps,
            "logit_rms_each": [sc["logit_rms_each"] for sc in scores]}


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
