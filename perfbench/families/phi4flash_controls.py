"""The controls of `correct` in the `phi4flash` family's cells: the faults
ISSUE 53 names, planted in the served path at the configuration's own
sizes, each judged as a run of the cell is judged.

What a run's `correct` rests on, and how a reading is made, is said in
families/keye_vl2_controls.py, whose `reference_prompts` and `four_bits`
this file uses, in families/minicpm_sala_controls.py, whose `serve` (slots
that have had an owner, the cases in flight together) it uses, and in
families/falcon_h1_controls.py, whose `judge` and whose loop over seeds it
uses: the engine is driven directly, the reference scores against the
SOUND weights, and the family's four numbers beside the count of tokens
(families/phi4flash.py `scored`) are taken with the fault planted
(`program_rows`, on the served tokens). A rounding is planted with
`lax.reduce_precision`, which XLA does not drop.

    python3 perfbench/families/phi4flash_controls.py \
        --workload phi-4-mini-flash-reasoning.reason-longctx \
        --seeds 11 12 13 --controls sound state_in_bf16 \
        --out chiprun_out/controls.jsonl
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# name -> what is planted
CONTROLS = {
    "sound": "nothing",
    "matmuls_below_bf16": "every matmul weight rounded to 4 significant "
                          "bits (a float8's; bf16 keeps 8)",
    "state_in_bf16": "an \"s6\" layer's state is kept in bf16 between "
                     "calls",
    "state_zeroed_at_tile_start": "every prefill tile's recurrence starts "
                                  "from a zero state",
    "tail_zeroed_at_tile_start": "every prefill tile's convolution starts "
                                 "from a zero tail",
    "lambda_at_its_init": "lambda_l is lambda0_l alone: the four learned "
                          "vectors are left out",
    "second_map_left_out": "a pair's output is its first map's alone",
    "pair_norm_skipped": "the difference is not normed",
    "one_minus_lambda0_left_out": "the normed difference is not scaled",
    "cross_reads_a_ring": "an \"xat\" layer reads the last window layer's "
                          "ring in place of the one cache by position",
    "units_fed_an_earlier_memory": "the \"gmu\" layers read the output of "
                                   "the second-to-last \"s6\" layer",
    "units_fed_the_gated_memory": "the \"gmu\" layers read the last "
                                  "\"s6\" layer's output AFTER its gate",
    "window_one_short": "a window layer attends one position fewer",
    "tail_on_the_tiles_last_row": "the cacheless layers run on a tile's "
                                  "LAST row, not on the prompt's last "
                                  "real row",
}
WARM = (600, 8)      # the slots' earlier owners: prompt, generated tokens


@contextlib.contextmanager
def planted(name: str, model, params, consume: bool = False):
    """-> (model, params) as served with the control `name` planted; the
    program's functions are the sound ones again on leaving. `consume`:
    a control that changes the weights may take `params`' own buffers."""
    import jax
    import jax.numpy as jnp

    from perfbench.families.keye_vl2_controls import four_bits
    from ray_tpu.models import TransformerLM, diff_attention as da, ssm, \
        transformer
    sound = (ssm.s6_scan, ssm.s6_step, ssm.causal_conv, da.combine,
             da._gate, dict(transformer.KIND_READS),
             transformer.Block._shared_mixer, TransformerLM._decode)
    scan, step, conv, combine, gate, _, mixer, decode = sound
    kinds = model.cfg.mixer_kinds
    last_s6 = max(i for i, k in enumerate(kinds) if k == "s6")

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
            ssm.s6_scan, ssm.s6_step = kept(scan), kept(step)
        elif name == "state_zeroed_at_tile_start":
            ssm.s6_scan = lambda x, dt, A, B, C, D, state, *a, **kw: scan(
                x, dt, A, B, C, D, jnp.zeros_like(state), *a, **kw)
        elif name == "tail_zeroed_at_tile_start":
            ssm.causal_conv = lambda x, tail, *a, **kw: conv(
                x, tail if x.shape[1] == 1 else jnp.zeros_like(tail),
                *a, **kw)
        elif name == "lambda_at_its_init":
            da.combine = lambda a, lam, lam0, *r: combine(a, lam0, lam0, *r)
        elif name == "second_map_left_out":
            da.combine = lambda a, lam, lam0, *r: combine(a, 0.0, lam0, *r)
        elif name == "pair_norm_skipped":
            def unnormed(a, lam, lam0, scale, eps):
                B, L, H, W = a.shape
                a32 = a.astype(jnp.float32).reshape(B, L, H // 2, 2, W)
                d = a32[..., 0, :] - lam * a32[..., 1, :]
                return (d * scale.astype(jnp.float32)
                        * (1.0 - lam0)).astype(a.dtype)
            da.combine = unnormed
        elif name == "one_minus_lambda0_left_out":
            da.combine = lambda a, lam, lam0, *r: combine(a, lam, 0.0, *r)
        elif name == "cross_reads_a_ring":
            transformer.KIND_READS["xat"] = "win"
        elif name in ("units_fed_an_earlier_memory",
                      "units_fed_the_gated_memory"):
            gated = []
            if name == "units_fed_the_gated_memory":
                def gate_and_keep(m, z):
                    gated.append(gate(m, z))
                    return gated[-1]
                da._gate = gate_and_keep

            def handed(self, normed, positions, cache, slots, real, shared):
                out, new = mixer(self, normed, positions, cache, slots,
                                 real, shared)
                if self.kind == "s6" and gated:
                    new = dict(new, mem=gated.pop())
                elif self.kind == "s6" and self.depth == last_s6 \
                        and name == "units_fed_an_earlier_memory":
                    new = dict(new, mem=shared["mem"])
                return out, new
            transformer.Block._shared_mixer = handed
        elif name == "window_one_short":
            model = with_cfg(window=model.cfg.window - 1)
        elif name == "tail_on_the_tiles_last_row":
            def last_row(self, x, positions, cache, embed, return_hidden,
                         chunked_prefill=False, logit_rows=None):
                if logit_rows is not None and x.shape[1] > 1:
                    n = len(cache["slots"]["idx"]) if "slots" in cache \
                        else 0
                    at = 0 if "slots" in cache else len(logit_rows) - 1
                    logit_rows = logit_rows.at[at].set(x.shape[1] - n - 1)
                return decode(self, x, positions, cache, embed,
                              return_hidden, chunked_prefill, logit_rows)
            TransformerLM._decode = last_row
        elif name != "sound":
            raise KeyError(f"{name!r} is none of {list(CONTROLS)}")
        yield model, params
    finally:
        (ssm.s6_scan, ssm.s6_step, ssm.causal_conv, da.combine, da._gate,
         reads, transformer.Block._shared_mixer,
         TransformerLM._decode) = sound
        transformer.KIND_READS.clear()
        transformer.KIND_READS.update(reads)


def judge(cfg: dict, params, cases, served, rows) -> dict:
    """families/falcon_h1_controls.py `judge` (serve_cell's reading of what
    was served, with the family's numbers taken while the fault was
    planted), and beside it this family's further numbers a case: the
    first scored row's deviation, and the state's and the tail's by layer
    ([first "s6" layer, last])."""
    from perfbench import spec
    from perfbench.families import falcon_h1_controls
    family = spec.family_of(cfg)
    kept = []

    def scored(*a, **kw):
        kept.append(family_scored(*a, **kw))
        return kept[-1]
    family_scored, family.scored = family.scored, scored
    try:
        row = falcon_h1_controls.judge(cfg, params, cases, served, rows)
    finally:
        family.scored = family_scored
    row.update(first_rms_by_case=[sc["first_rms"] for sc in kept],
               state_rel_by_layer=[sc["state_rel_by_layer"] for sc in kept],
               tail_rel_by_layer=[sc["tail_rel_by_layer"] for sc in kept])
    return row


def readings(cfg: dict, mix: dict, seed: int, controls):
    """One row a control at this seed (families/falcon_h1_controls.py
    `readings`, with this family's `planted`)."""
    import gc
    import time

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
            # `params` were consumed: nothing of the old tree or of the
            # program's rows stays on the device while the sound weights
            # are drawn anew
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
    import argparse
    import json
    import time
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
