"""The controls of `correct` in the `minicpm_sala` family's cells: the
faults ISSUE 39 names, planted in the served path at the configuration's
own sizes, each judged as a run of the cell is judged.

What a run's `correct` rests on, and how a reading is made, is said in
families/keye_vl2_controls.py, whose `reference_prompts` and `four_bits`
this file uses: the engine is driven directly, the cases in flight
together, the reference scores against the SOUND weights. This family's
comparison has two more numbers, the distance of the program's own logits
and of its first lightning state from the reference's (families/
minicpm_sala.py `scored`): both are taken with the fault planted
(`program_rows`, on the served tokens), and `judge` folds the three as a
run of the cell does.

    python3 perfbench/families/minicpm_sala_controls.py \
        --workload minicpm-sala.longdoc-pool --seeds 11 12 13 \
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
    "attends_every_live_position": "a sparse layer attends every live "
                                   "position and not the selected blocks",
    "pooled_keys_shifted_by_one": "the selection reads the pooled keys one "
                                  "kernel off",
    "state_zeroed_at_tile_start": "every prefill tile of a lightning layer "
                                  "starts from a zero state",
    "state_of_last_owner_left": "a slot keeps its last owner's lightning "
                                "states when a new request takes it",
    "state_in_bf16": "the lightning state is kept in bf16 between calls",
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
    from ray_tpu.models import (TransformerLM, linear_attention as la,
                                sparse_attention as sa)
    sound = (sa.block_select, la.lightning_scan, la.lightning_step,
             kv_cache.SlotPool.insert)
    try:
        if name == "matmuls_below_bf16":
            rounded = jax.jit(four_bits,
                              donate_argnums=(0,) if consume else ())
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: rounded(a)
                if path[-1].key == "kernel" else a, params)
        elif name == "attends_every_live_position":
            model = TransformerLM(dataclasses.replace(
                model.cfg, blk_topk=10 ** 6))
        elif name == "pooled_keys_shifted_by_one":
            sa.block_select = lambda q, kp, qpos, geo: sound[0](
                q, jnp.roll(kp, 1, axis=1), qpos, geo)
        elif name == "state_zeroed_at_tile_start":
            la.lightning_scan = lambda q, k, v, state, *a, **kw: sound[1](
                q, k, v, jnp.zeros_like(state), *a, **kw)
        elif name == "state_of_last_owner_left":
            def insert(pool, scratch, slot):
                at = list(pool.shapes).index("s")
                scratch = list(scratch)
                # (a copy: of a pool of one slot the slice is the pool,
                # which the insert donates)
                scratch[at] = jnp.copy(pool.s[:, slot:slot + 1])
                return sound[3](pool, scratch, slot)
            kv_cache.SlotPool.insert = insert
        elif name == "state_in_bf16":
            def coarse(s):
                # not .astype(bfloat16).astype(float32): on the TPU XLA
                # drops a convert there and back inside one program
                # (xla_allow_excess_precision), and the fault with it
                return jax.lax.reduce_precision(s, exponent_bits=8,
                                                mantissa_bits=7)

            def kept(fn):
                def run(q, k, v, state, *a, **kw):
                    o, new = fn(q, k, v, coarse(state), *a, **kw)
                    return o, coarse(new)
                return run
            la.lightning_scan = kept(sound[1])
            la.lightning_step = kept(sound[2])
        elif name != "sound":
            raise KeyError(f"{name!r} is none of {list(CONTROLS)}")
        yield model, params
    finally:
        (sa.block_select, la.lightning_scan, la.lightning_step,
         kv_cache.SlotPool.insert) = sound


def serve(model, params, cfg: dict, cases, seed: int, warm=WARM):
    """The greedy tokens of each case out of a fresh engine whose slots
    have had an owner each before (so that a slot has a last owner's
    state to inherit, where a fault lets it)."""
    import numpy as np

    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    engine = {k: v for k, v in cfg["engine"].items()
              if k != "max_ongoing_requests"}
    eng = InferenceEngine(model, params, EngineConfig(**engine))
    rng = np.random.default_rng([int(seed), 98])
    for batch in ([(rng.integers(1, cfg["vocab_size"], size=warm[0]),
                    warm[1])] * len(cases), cases):
        handles = [eng.submit(np.asarray(p), max_new_tokens=g)
                   for p, g in batch]
        while eng.sched.has_work():
            eng.step()
        out = [list(h) for h in handles]
    del eng, handles
    gc.collect()
    return out


def judge(cfg: dict, params, cases, served, rows) -> dict:
    """serve_cell's reading of what was served: replica.bench_reference's
    padding, the family's gaps (with `rows`, what `program_rows` gave a case
    each, taken while the fault was planted), the share within the
    configuration's `logit_gap` and whether it reaches `share_within`;
    beside it each number alone: the tokens whose own gap is beyond, and a
    case's logit deviation against `logit_rms`."""
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
            "state_rel_by_case": [family.state_number(sc["state_rel"])
                                  for sc in scores],
            "state_rel_limit": tol["state_rel"],
            "gaps": gaps,
            "logit_rms_each": [sc["logit_rms_each"] for sc in scores],
            "state_rel": [sc["state_rel"] for sc in scores]}


def readings(cfg: dict, mix: dict, seed: int, controls):
    """One row a control at this seed."""
    from perfbench import spec, weights
    from perfbench.families.keye_vl2_controls import reference_prompts
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
        gc.collect()
        if name == "matmuls_below_bf16":          # `params` were consumed
            params = weights.seeded_params(model, seed, family.weight_rule)
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
