"""The controls of `correct` in the `ling3` family's cells: the faults
ISSUE 58 names, planted in the served path at the configuration's own sizes,
each judged as a run of the cell is judged.

What a run's `correct` rests on, and how a reading is made, is said in
families/keye_vl2_controls.py, whose `reference_prompts` and `four_bits`
this file uses, and in families/minicpm_sala_controls.py, whose `serve`
(slots that have had an owner, the cases in flight together) it uses: the
engine is driven directly, the reference scores against the SOUND weights,
and the family's numbers beside the count of tokens (families/ling3.py
`scored`) are taken with the fault planted (`program_rows`). A control's
tokens are the engine's own with the fault planted, or, with `--forced`,
the SOUND engine's of the same seed (the program's numbers hardly depend on
which tokens are forced, and an engine's start with a fault planted compiles
both step programs anew, a minute and more a control a seed). A rounding is
planted with `lax.reduce_precision`, which XLA does not drop.

    python3 perfbench/families/ling3_controls.py \
        --workload ling-3.0-flash-vl.longctx-wide --seeds 11 12 13 \
        --controls sound state_in_bf16 --out chiprun_out/controls.jsonl
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
    "state_in_bf16": "a \"kda\" layer's state is kept in bf16 between calls",
    "state_zeroed_at_tile_start": "every prefill tile's scan starts from a "
                                  "zero state",
    "tail_zeroed_at_tile_start": "every prefill tile's convolutions start "
                                 "from a zero tail",
    "delta_term_left_out": "S_t = Diag(alpha) S + beta k v^T: the row's "
                           "value is not lessened by what the state holds",
    "decay_a_heads_mean": "the log-decay is its head's mean over the "
                          "channels, one number a head",
    "beta_fixed_at_one": "beta is 1 for every row a request owns",
    "k_not_normalised": "k enters the recurrence as convolved, not at unit "
                        "length",
    "gate_without_the_bound": "g = -exp(A) softplus(a + b), the gate "
                              "without the lower bound",
    "output_gate_skipped": "a KDA head's normed output is not gated",
    "no_groups": "the 8 experts are the largest of all 512, no groups",
    "group_scored_by_its_largest": "a group's score is its ONE largest "
                                   "score + bias",
    "bias_weighs": "the taken experts are weighed by score + bias",
    "scale_left_out": "the gates are not multiplied by "
                      "routed_scaling_factor",
    "latent_pool_three_bits": "every latent row, as attended and as "
                              "cached, rounded to 3 mantissa bits",
}
WARM = (600, 8)      # the slots' earlier owners: prompt, generated tokens
_ROUNDED = ("kernel", "gate", "up", "down", "kv_up")


def _rowwise(delta: bool):
    """`kda_scan` / `kda_step` as the recurrence row by row (a `lax.scan`
    over the rows), with the delta term left out where not `delta`."""
    import jax
    import jax.numpy as jnp

    def scan(q, k, v, g, beta, state, real=None, *_):
        if real is not None:
            g = g * real[:, :, None, None]
            beta = beta * real[:, :, None]

        def row(S, xs):
            q, k, v, g, beta = xs
            S = jnp.exp(g)[..., None] * S
            back = jnp.sum(S * k[..., None], axis=-2) if delta else 0.0
            S = S + k[..., None] * (beta[..., None] * (v - back))[..., None, :]
            return S, jnp.sum(S * q[..., None], axis=-2)

        with jax.named_scope("kda_scan"):
            state, o = jax.lax.scan(row, state, tuple(
                jnp.swapaxes(a, 0, 1)
                for a in (q, k, v.astype(jnp.float32), g, beta)))
            return jnp.swapaxes(o, 0, 1), state

    def step(q, k, v, g, beta, state, real=None):
        return scan(q, k, v, g, beta, state,
                    None if real is None else real[:, None])

    return scan, step


@contextlib.contextmanager
def planted(name: str, model, params, consume: bool = False):
    """-> (model, params) as served with the control `name` planted; the
    program's functions are the sound ones again on leaving. `consume`:
    a control that changes the weights may take `params`' own buffers
    (two copies of the served weights do not fit the chip)."""
    import jax
    import jax.numpy as jnp

    from perfbench.families.keye_vl2_controls import four_bits
    from ray_tpu.models import (TransformerLM, kda, latent_attention as la,
                                moe, ssm)
    sound = (kda.kda_scan, kda.kda_step, kda.kda_gate, kda.unit,
             kda.gated_norm, ssm.causal_conv, moe.sigmoid_route,
             la.latent_rows)
    scan, step, gate, unit, gated_norm, conv, route, rows_of = sound

    def with_cfg(**over):
        return TransformerLM(dataclasses.replace(model.cfg, **over))

    try:
        if name == "matmuls_below_bf16":
            rounded = jax.jit(four_bits,
                              donate_argnums=(0,) if consume else ())
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: rounded(a)
                if path[-1].key in _ROUNDED else a, params)
        elif name == "state_in_bf16":
            def coarse(s):
                return jax.lax.reduce_precision(s, exponent_bits=8,
                                                mantissa_bits=7)

            def kept(fn):
                def run(q, k, v, g, beta, state, *a, **kw):
                    o, new = fn(q, k, v, g, beta, coarse(state), *a, **kw)
                    return o, coarse(new)
                return run
            kda.kda_scan, kda.kda_step = kept(scan), kept(step)
        elif name == "state_zeroed_at_tile_start":
            kda.kda_scan = lambda q, k, v, g, beta, state, *a, **kw: scan(
                q, k, v, g, beta, jnp.zeros_like(state), *a, **kw)
        elif name == "tail_zeroed_at_tile_start":
            ssm.causal_conv = lambda x, tail, *a, **kw: conv(
                x, tail if x.shape[1] == 1 else jnp.zeros_like(tail),
                *a, **kw)
        elif name == "delta_term_left_out":
            kda.kda_scan, kda.kda_step = _rowwise(delta=False)
        elif name == "decay_a_heads_mean":
            def mean_gate(*a):
                g = gate(*a)
                return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
            kda.kda_gate = mean_gate
        elif name == "beta_fixed_at_one":
            kda.kda_scan = lambda q, k, v, g, beta, *a, **kw: scan(
                q, k, v, g, jnp.ones_like(beta), *a, **kw)
            kda.kda_step = lambda q, k, v, g, beta, *a, **kw: step(
                q, k, v, g, jnp.ones_like(beta), *a, **kw)
        elif name == "k_not_normalised":
            # (q is normed to head_dim^-1/2, k to 1: the scale tells them)
            kda.unit = lambda x, scale=1.0: unit(x, scale) \
                if scale != 1.0 else x.astype(jnp.float32)
        elif name == "gate_without_the_bound":
            kda.kda_gate = lambda a, A_log, bias, floor: -jnp.exp(
                A_log.astype(jnp.float32))[:, None] * jax.nn.softplus(
                a.astype(jnp.float32) + bias.astype(jnp.float32))
        elif name == "output_gate_skipped":
            kda.gated_norm = lambda o, scale, g, eps: gated_norm(
                o, scale, jnp.ones_like(g), eps)
        elif name == "no_groups":
            model = with_cfg(n_group=1, topk_group=1)
        elif name == "group_scored_by_its_largest":
            def by_one(x, router, bias, k, n_group=1, topk_group=1):
                scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router)
                choose = scores + bias
                by_group = choose.reshape(choose.shape[:-1] + (n_group, -1))
                _, kept = jax.lax.top_k(by_group.max(-1), topk_group)
                stays = jnp.any(kept[..., None] == jnp.arange(n_group), -2)
                choose = jnp.where(stays[..., None], by_group,
                                   -jnp.inf).reshape(choose.shape)
                _, taken = jax.lax.top_k(choose, k)
                return scores, jnp.take_along_axis(scores, taken, -1), taken
            moe.sigmoid_route = by_one
        elif name == "bias_weighs":
            def weighed(x, router, bias, k, *groups):
                scores, _, taken = route(x, router, bias, k, *groups)
                return scores, jnp.take_along_axis(
                    scores + bias, taken, axis=-1), taken
            moe.sigmoid_route = weighed
        elif name == "scale_left_out":
            model = with_cfg(route_scale=1.0)
        elif name == "latent_pool_three_bits":
            la.latent_rows = lambda *a: jax.lax.reduce_precision(
                rows_of(*a), exponent_bits=8, mantissa_bits=3)
        elif name != "sound":
            raise KeyError(f"{name!r} is none of {list(CONTROLS)}")
        yield model, params
    finally:
        (kda.kda_scan, kda.kda_step, kda.kda_gate, kda.unit, kda.gated_norm,
         ssm.causal_conv, moe.sigmoid_route, la.latent_rows) = sound


def judge(cfg: dict, params, cases, served, rows) -> dict:
    """serve_cell's reading of what was served: replica.bench_reference's
    padding, the family's gaps (with `rows`, what `program_rows` gave a
    case each, taken while the fault was planted), the share within the
    configuration's `logit_gap` and whether it reaches `share_within`;
    beside it each number alone, a case."""
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
    row = {"n_tokens": len(flat), "share_within_gap": share,
           "beyond": sum(x > tol["logit_gap"] for x in flat),
           "passes": share >= tol["share_within"],
           "tokens_beyond_by_case": [sum(
               x > tol["logit_gap"] for x in sc["gaps"]) for sc in scores],
           "spread": [sc["spread"] for sc in scores], "gaps": gaps,
           "logit_rms_each": [sc["logit_rms_each"] for sc in scores]}
    for key in ("logit_rms", "edge_rms") + family.NUMBERS:
        row[key + "_by_case"] = [sc[key] for sc in scores]
        row["over_" + key] = max(sc[key] for sc in scores) / tol[
            "logit_rms" if key == "edge_rms" else key]
    return row


def readings(cfg: dict, mix: dict, seed: int, controls, forced=False):
    """One row a control at this seed. `forced`: the controls' program rows
    are teacher-forced on the SOUND engine's tokens (served once a seed)
    and no engine is started with a fault planted."""
    import gc
    import time

    from perfbench import spec, weights
    from perfbench.families.keye_vl2_controls import reference_prompts
    from perfbench.families.minicpm_sala_controls import serve
    family = spec.family_of(cfg)
    model = family.build_model(family.model_kwargs(cfg))
    params = weights.seeded_params(model, seed, family.weight_rule)
    cases = reference_prompts(mix, cfg, seed)
    sound_tokens = serve(model, params, cfg, cases, seed, WARM) \
        if forced else None
    for name in controls:
        t0 = time.monotonic()
        family._programs.cache_clear()    # a planted function is traced anew
        try:
            with planted(name, model, params, consume=True) as (
                    m, served_params):
                served = sound_tokens or serve(m, served_params, cfg, cases,
                                               seed, WARM)
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
        yield dict(row, control=name, seed=seed, forced=bool(forced),
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
    ap.add_argument("--forced", action="store_true",
                    help="teacher-force every control on the sound engine's "
                    "tokens of the seed")
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
        for row in readings(cfg, mix, seed, args.controls, args.forced):
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
