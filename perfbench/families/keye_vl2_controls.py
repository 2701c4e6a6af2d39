"""The controls of `correct` in the `keye_vl2` family's cells: the faults
ISSUE 34 names, planted in the served path at the configuration's own
sizes, each judged as a run of the cell is judged.

A run's `correct` rests on one comparison (perfbench/serve_cell.py): the
engine serves the mix's `reference_cases` greedily, the family's reference
scores prompt + served tokens in one teacher-forced pass, and the share of
served tokens whose reference logit lies within `logit_gap` of their
position's largest must reach `share_within`. This file makes that reading
for the sound program and for each fault, on the replica's seeded weights
and on the prompts the harness draws from the same `--seed`, so that the
limit in the configuration's `reference_tolerance` can be set between the
two and shown to hold:

    python3 perfbench/families/keye_vl2_controls.py \
        --workload keye-vl-2.0-30b-a3b.longdoc-mixed --seeds 11 12 13 \
        --controls sound matmuls_below_bf16 --out chiprun_out/controls.jsonl

The engine is driven directly (no Serve plane: it changes no token), the
cases are in flight together (a request among others gives the tokens it
gives alone: tests/test_sparse_moe_model.py), and everything else is the
harness's: `spec`, the family's `model_kwargs` / `build_model` /
`weight_rule` / `teacher_forced_gaps`, `weights.seeded_params`, the padding
of `replica.bench_reference`, the arithmetic of `serve_cell`. The same
faults are CPU tests at a small size in float32, where the sound program
leaves every token at a gap of 0 (tests/test_sparse_moe_model.py).
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

# name -> what is planted. The first three change the program (a new
# engine traces it), the last two the weights the engine serves; the
# reference always scores against the sound weights.
CONTROLS = {
    "sound": "nothing",
    "attends_every_live_position": "attention over every live position "
                                   "and not the selected ones",
    "indexer_keys_shifted_by_one": "the indexer's keys read one position "
                                   "off",
    "indexer_keys_of_another_slot": "decode reads the next slot's indexer "
                                    "keys",
    "an_expert_dropped": "one held expert's output projection zeroed",
    "matmuls_below_bf16": "every matmul weight rounded to 4 significant "
                          "bits (a float8's; bf16 keeps 8)",
}


def four_bits(a):
    """`a` rounded to 4 significant bits, in its own type."""
    import jax.numpy as jnp
    mant, exp = jnp.frexp(a.astype(jnp.float32))
    return jnp.ldexp(jnp.round(mant * 16.0) / 16.0, exp).astype(a.dtype)


@contextlib.contextmanager
def planted(name: str, model, params):
    """-> (model, params) as served with the control `name` planted; the
    program's `index_scores` is the sound one again on leaving."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerLM, sparse_attention as sa
    sound = sa.index_scores
    try:
        if name == "attends_every_live_position":
            model = TransformerLM(dataclasses.replace(
                model.cfg, index_topk=10 ** 6))
        elif name == "indexer_keys_shifted_by_one":
            sa.index_scores = lambda qi, w, ki, qpos, layer=None: sound(
                qi, w, jnp.roll(ki, 1, axis=-1), qpos, layer)
        elif name == "indexer_keys_of_another_slot":
            # with `layer` the keys are the pool, [n_layers, slots, ..]
            sa.index_scores = lambda qi, w, ki, qpos, layer=None: sound(
                qi, w, ki if layer is None else jnp.roll(ki, 1, axis=1),
                qpos, layer)
        elif name == "an_expert_dropped":
            block = params["layers"]["block"]
            moe = block["moe"]
            held = moe["down"].shape[1]
            params = dict(params, layers={"block": dict(block, moe=dict(
                moe, down=moe["down"].at[:, held // 4].set(0)))})
        elif name == "matmuls_below_bf16":
            params = jax.tree.map(
                lambda a: four_bits(a) if a.ndim > 2 else a, params)
        elif name != "sound":
            raise KeyError(f"{name!r} is none of {list(CONTROLS)}")
        yield model, params
    finally:
        sa.index_scores = sound


def reference_prompts(mix: dict, cfg: dict, seed: int):
    """The mix's reference cases as serve_cell draws them from `--seed`
    (the probe's prompt comes first out of the same generator)."""
    import numpy as np

    from perfbench.serve_cell import PROBE_PROMPT, REFERENCE_CASES
    vocab, max_len = cfg["vocab_size"], cfg["engine"]["max_len"]
    rng = np.random.default_rng([int(seed), 99])
    rng.integers(1, vocab, size=min(PROBE_PROMPT, max_len // 2))
    return [(rng.integers(1, vocab, size=min(p, max_len // 2)).tolist(), g)
            for p, g in mix.get("reference_cases", REFERENCE_CASES)]


def serve(model, params, cfg: dict, cases, n_new=None):
    """The greedy tokens of each case out of a fresh engine."""
    import numpy as np

    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    engine = {k: v for k, v in cfg["engine"].items()
              if k != "max_ongoing_requests"}
    eng = InferenceEngine(model, params, EngineConfig(**engine))
    handles = [eng.submit(np.asarray(p), max_new_tokens=n_new or g)
               for p, g in cases]
    while eng.sched.has_work():
        eng.step()
    out = [list(h) for h in handles]
    del eng, handles
    gc.collect()
    return out


def judge(cfg: dict, params, cases, served) -> dict:
    """serve_cell's reading of what was served: replica.bench_reference's
    padding, the family's gaps, the share within the configuration's
    `logit_gap` and whether it reaches `share_within`."""
    from perfbench import spec
    gaps_of = spec.family_of(cfg).teacher_forced_gaps
    pad = max(len(p) + len(g) for (p, _), g in zip(cases, served))
    pad = -(-pad // 128) * 128
    gaps = [gaps_of(params, cfg, p, g, pad_to=pad)
            for (p, _), g in zip(cases, served)]
    tol = cfg["reference_tolerance"]
    flat = [x for g in gaps for x in g]
    share = sum(x <= tol["logit_gap"] for x in flat) / len(flat)
    return {"n_tokens": len(flat), "logit_gap": tol["logit_gap"],
            "share_within_gap": share, "beyond": sum(
                x > tol["logit_gap"] for x in flat),
            "passes": share >= tol["share_within"], "max_gap": max(flat),
            "argmax_share": sum(x == 0.0 for x in flat) / len(flat),
            "gaps": gaps}


def readings(cfg: dict, mix: dict, seed: int, controls, n_new=None):
    """One row a control at this seed (`n_new`: tokens a case, where not
    the mix's own)."""
    from perfbench import spec, weights
    family = spec.family_of(cfg)
    model = family.build_model(family.model_kwargs(cfg))
    params = weights.seeded_params(model, seed, family.weight_rule)
    cases = reference_prompts(mix, cfg, seed)
    for name in controls:
        t0 = time.monotonic()
        with planted(name, model, params) as (m, served_params):
            served = serve(m, served_params, cfg, cases, n_new)
            del served_params
        gc.collect()
        row = judge(cfg, params, cases, served)
        yield dict(row, control=name, seed=seed,
                   seconds=round(time.monotonic() - t0, 1))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", default=list(CONTROLS),
                    choices=list(CONTROLS))
    ap.add_argument("--new-tokens", type=int, default=None,
                    help="tokens a case, where not the mix's own")
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
        for row in readings(cfg, mix, seed, args.controls,
                            args.new_tokens):
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            bad += row["passes"] != (row["control"] == "sound")
            print("CONTROL " + json.dumps(
                {k: v for k, v in row.items() if k != "gaps"}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
