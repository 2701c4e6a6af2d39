"""Elastic MPMD pipeline probe (bench.py subprocess).

Measures the in-process MPMD pipeline (train/mpmd.py LocalStageHandle —
the transport-independent half of the trainer; the actor gang adds RPC
hops, not different math) on the virtual CPU mesh:

  - steady-state step latency (median ms/step, first compile step
    excluded) and steps/s for the plain 1F1B schedule — the headline
    series, unchanged since r05
  - measured per-stage bubble fraction (1 - compute/wall) next to BOTH
    analytic bounds: plain (S-1)/(M+S-1) and interleaved
    (S-1)/(v*M+S-1)
  - the interleaved-vs-plain comparison (`vs_plain_1f1b`): the SAME
    total model run both ways — S stages of v layers plain, V = S*v
    single-layer virtual stages interleaved — with the parallel step
    time MODELED by pipeline.simulate_timeline fed the MEASURED per-op
    durations (this box has one core; serial wall cannot show schedule
    overlap, the event-timeline model is the physics the bubble bound
    approximates). The comparison runs at a compute-dominated size
    (`cmp_d_model`/`cmp_microbatch`, default 1024/32 — per-op compute
    >> the ~40us dispatch overhead v-way interleaving doubles), while
    the headline series stays at the r05 size; interleaving pays
    exactly when per-chunk compute dominates per-op overhead, and the
    probe reports both sizes so that boundary is visible
  - `checkpoint_off_step_ms`: per-step time (compile step excluded,
    boundary call inside the timed region, big-state model) with
    checkpointing off vs every-step async (off the hot path) vs
    every-step sync — the off-step I/O effect
  - donation on/off step time (no-op on CPU, the audit signal on TPU)
  - recovery under ONE injected stage kill mid-step AT v=2 (chaos
    StageKiller shape, armed deterministically): steps lost (replayed)
    and wall-clock recovery time, with the bit-identity + per-virtual-
    chunk compile-once acceptance checks asserted inline — a probe
    that reports numbers from a run that diverged would be worse than
    no probe.

Usage: python pipeline_probe.py --one '{"n_stages": 2,
    "n_microbatches": 8, "steps": 10, "d_model": 64, "runs": 3, "v": 2}'
Prints one line: RESULT {json}
"""

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _builders(n_virtual, d_model, n_layers_per_stage=1):
    import jax
    import jax.numpy as jnp
    import optax

    def builder(stage_idx):
        from ray_tpu.train.mpmd import StageDefinition
        ks = jax.random.split(jax.random.PRNGKey(stage_idx + 1),
                              n_layers_per_stage)
        params = [{"w": jax.random.normal(k, (d_model, d_model)) * 0.3,
                   "b": jnp.zeros((d_model,))} for k in ks]

        def stage_fn(ps, x):
            for p in ps:
                x = jnp.tanh(x @ p["w"] + p["b"])
            return x

        loss_fn = None
        if stage_idx == n_virtual - 1:
            def loss_fn(y, t):
                return jnp.mean((y - t) ** 2)
        return StageDefinition(stage_fn=stage_fn, params=params,
                               optimizer=optax.adamw(1e-3),
                               loss_fn=loss_fn)
    return builder


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else 0.0


def _fit_stats(tr, out):
    """(median wall ms/step excl. compile step, mean per-stage bubble,
    per-(stage, chunk) mean fwd/bwd op seconds from the last step)."""
    walls = [h["wall_s"] for h in out["history"][1:]]
    med_ms = _median(walls) * 1e3
    S = tr.n_stages
    bubble = []
    for s in range(S):
        fr = [h[f"stage{s}_bubble_fraction"] for h in out["history"][1:]]
        bubble.append(sum(fr) / len(fr))
    op_s = {}
    for s, per_chunk in enumerate(tr.last_stage_metrics):
        for c, m in enumerate(per_chunk):
            op_s[(s, c)] = {
                "F": m["fwd_s"] / max(1, m["fwd_n"]),
                "B": m["bwd_s"] / max(1, m["bwd_n"]),
            }
    return med_ms, bubble, op_s


def run(spec):
    import time

    import numpy as np

    from ray_tpu.parallel.pipeline import (OP_FWD, make_schedule,
                                           pipeline_bubble_fraction,
                                           simulate_timeline)
    from ray_tpu.train.config import FailureConfig
    from ray_tpu.train.mpmd import MPMDConfig, MPMDPipelineTrainer

    S = int(spec.get("n_stages", 2))
    M = int(spec.get("n_microbatches", 8))
    steps = int(spec.get("steps", 10))
    d_model = int(spec.get("d_model", 64))
    mb = int(spec.get("microbatch", 8))
    runs = int(spec.get("runs", 3))
    v = int(spec.get("v", 2))
    V = S * v
    cmp_S = int(spec.get("cmp_n_stages", max(S, 4)))
    cmp_V = cmp_S * v
    cmp_d = int(spec.get("cmp_d_model", 1024))
    cmp_mb = int(spec.get("cmp_microbatch", 32))
    cmp_steps = int(spec.get("cmp_steps", max(4, steps // 2)))

    # headline legs stay at the r05 size; the schedule comparison runs
    # the same total model both ways at a compute-dominated,
    # deeper-pipeline size (the bubble saving scales with S-1, the
    # per-op hand-off overhead interleaving doubles does not):
    # plain = cmp_S hosts x v layers, interleaved = cmp_V single-layer
    # virtual stages on cmp_S hosts
    plain_builder = _builders(S, d_model, n_layers_per_stage=v)
    inter_builder = _builders(V, d_model, n_layers_per_stage=1)
    cmp_plain_builder = _builders(cmp_S, cmp_d, n_layers_per_stage=v)
    cmp_inter_builder = _builders(cmp_V, cmp_d, n_layers_per_stage=1)

    def data_fn_of(width, batch):
        def data_fn(step):
            rng = np.random.RandomState(step)
            ins = [rng.randn(batch, width).astype(np.float32)
                   for _ in range(M)]
            tgts = [rng.randn(batch, width).astype(np.float32)
                    for _ in range(M)]
            return ins, tgts
        return data_fn

    data_fn = data_fn_of(d_model, mb)
    cmp_data_fn = data_fn_of(cmp_d, cmp_mb)

    fc = FailureConfig(max_failures=2, restart_policy="stage",
                       restart_backoff_s=0.0)

    def mk_cfg(**kw):
        kw.setdefault("n_microbatches", M)
        kw.setdefault("replay_depth", 2)
        return MPMDConfig(**kw)

    # --- plain 1F1B: headline latency + measured op durations ---------
    plain_meds, plain_bubbles, plain_ops = [], [], []
    for _rep in range(runs):
        tr = MPMDPipelineTrainer([plain_builder] * S, mk_cfg(), fc)
        out = tr.fit(data_fn, steps)
        med, bub, ops = _fit_stats(tr, out)
        plain_meds.append(med)
        plain_bubbles.append(bub)
        plain_ops.append(ops)
        for counts in tr.compile_counts():
            assert counts["fwd"] == 1 and counts["bwd"] == 1, counts
    step_ms = _median(plain_meds)
    bubble = [round(sum(b[s] for b in plain_bubbles) / len(plain_bubbles),
                    4) for s in range(S)]

    # --- interleaved v-way over the same total model, at the
    # compute-dominated comparison size --------------------------------
    cmp_plain_meds, cmp_plain_ops = [], []
    inter_meds, inter_ops = [], []
    for _rep in range(runs):
        tr = MPMDPipelineTrainer([cmp_plain_builder] * cmp_S, mk_cfg(), fc)
        out = tr.fit(cmp_data_fn, cmp_steps)
        med, _bub, ops = _fit_stats(tr, out)
        cmp_plain_meds.append(med)
        cmp_plain_ops.append(ops)
        tr = MPMDPipelineTrainer([cmp_inter_builder] * cmp_V,
                                 mk_cfg(virtual_stages=v), fc)
        out = tr.fit(cmp_data_fn, cmp_steps)
        med, _bub, ops = _fit_stats(tr, out)
        inter_meds.append(med)
        inter_ops.append(ops)
        for counts in tr.compile_counts():        # per VIRTUAL chunk
            assert counts["fwd"] == 1 and counts["bwd"] == 1, counts
    inter_step_ms = _median(inter_meds)

    # --- modeled parallel spans from the measured per-op durations ----
    def op_time_of(samples):
        def op_time(s, kind, chunk):
            key = "F" if kind == OP_FWD else "B"
            return _median([rep[(s, chunk)][key] for rep in samples])
        return op_time

    plain_tl = simulate_timeline(make_schedule("1f1b", cmp_S, M),
                                 op_time_of(cmp_plain_ops))
    inter_tl = simulate_timeline(make_schedule("1f1b", cmp_S, M,
                                               virtual=v),
                                 op_time_of(inter_ops))
    vs_plain = (inter_tl["span"] / plain_tl["span"]
                if plain_tl["span"] else 0.0)
    assert vs_plain < 1.0, (
        f"interleaved modeled span {inter_tl['span']:.6f}s not below "
        f"plain {plain_tl['span']:.6f}s (vs_plain_1f1b={vs_plain:.3f})")

    # --- off-step checkpoint I/O: per-step time on vs off -------------
    # Drives the trainer's own step loop directly so the compile step
    # is excluded cleanly and the boundary-checkpoint call is INSIDE
    # the timed region (fit() hides it between history rows). Uses the
    # big-state builders — a 64-wide stage snapshots in microseconds,
    # which would measure nothing.
    ck_steps = int(spec.get("ck_steps", 6))
    ck_builder = _builders(S, cmp_d, n_layers_per_stage=v)

    def stepped_ms(every, **cfg_kw):
        tr = MPMDPipelineTrainer([ck_builder] * S,
                                 mk_cfg(**cfg_kw), fc)
        tr.start()
        times = []
        for step in range(1, ck_steps + 1):
            ins, tgts = cmp_data_fn(step)
            tr.replay.record(step, ins, tgts)
            t0 = time.perf_counter()
            tr._run_step_with_recovery(step, ins, tgts)
            if every and step % every == 0:
                tr._checkpoint_all(step)
            if step > 1:               # step 1 pays the compiles
                times.append(time.perf_counter() - t0)
        return _median(times) * 1e3

    ck_off = stepped_ms(0, checkpoint_every=ck_steps + 1,
                        replay_depth=ck_steps + 1)
    ck_async = stepped_ms(1, checkpoint_every=1, async_checkpoint=True)
    ck_sync = stepped_ms(1, checkpoint_every=1, async_checkpoint=False)

    # --- donation on/off (CPU: parity check; TPU: the audit signal) ---
    donate_off_ms = stepped_ms(0, checkpoint_every=ck_steps + 1,
                               replay_depth=ck_steps + 1,
                               donate_buffers=False)

    # --- recovery under one injected mid-step stage kill, AT v=2 ------
    base = MPMDPipelineTrainer([inter_builder] * V,
                               mk_cfg(virtual_stages=v), fc)
    base.fit(data_fn, steps)
    kill_step = max(3, steps // 2)
    tr = MPMDPipelineTrainer([inter_builder] * V,
                             mk_cfg(virtual_stages=v), fc)
    tr.start()
    tr.handles[S - 1]._fail_at = (kill_step, "F")
    t0 = time.perf_counter()
    out = tr.fit(data_fn, steps)
    elastic_wall_s = time.perf_counter() - t0
    assert out["recoveries"], "injected stage kill never fired"
    rec = out["recoveries"][0]
    assert tr.state_digests() == base.state_digests(), \
        "post-recovery state diverged from uninterrupted interleaved run"
    for counts in tr.compile_counts():   # ==1 per virtual chunk, still
        assert counts["fwd"] == 1 and counts["bwd"] == 1, counts

    spread = ((max(plain_meds) - min(plain_meds)) / step_ms
              if step_ms else 0.0)
    return {
        "mpmd_pipeline_step_ms": round(step_ms, 3),
        "steps_per_s": round(1e3 / step_ms, 3) if step_ms else 0.0,
        "n_stages": S, "n_microbatches": M,
        "schedule": "1f1b",
        "bubble_fraction_per_stage": bubble,
        "bubble_fraction_analytic": round(
            pipeline_bubble_fraction(S, M), 4),
        "bubble_fraction_analytic_interleaved": round(
            pipeline_bubble_fraction(S, M, virtual=v), 4),
        "interleaved": {
            "v": v,
            "cmp_n_stages": cmp_S,
            "cmp_d_model": cmp_d, "cmp_microbatch": cmp_mb,
            "plain_step_ms_serial": round(_median(cmp_plain_meds), 3),
            "step_ms_serial": round(inter_step_ms, 3),
            "modeled_plain_span_ms": round(plain_tl["span"] * 1e3, 3),
            "modeled_interleaved_span_ms": round(
                inter_tl["span"] * 1e3, 3),
            "modeled_bubble_plain": round(
                plain_tl["bubble_fraction"], 4),
            "modeled_bubble_interleaved": round(
                inter_tl["bubble_fraction"], 4),
            "analytic_bubble_plain": round(
                pipeline_bubble_fraction(cmp_S, M), 4),
            "analytic_bubble_interleaved": round(
                pipeline_bubble_fraction(cmp_S, M, virtual=v), 4),
        },
        "vs_plain_1f1b": round(vs_plain, 4),
        "checkpoint_off_step_ms": {
            "d_model": cmp_d,
            "ckpt_off": round(ck_off, 3),
            "ckpt_async": round(ck_async, 3),
            "ckpt_sync": round(ck_sync, 3),
            "async_overhead_ms": round(ck_async - ck_off, 3),
            "sync_overhead_ms": round(ck_sync - ck_off, 3),
        },
        "donate_off_step_ms": round(donate_off_ms, 3),
        "donate_on_step_ms": round(ck_off, 3),
        "spread": round(spread, 3),
        "runs": [round(r, 3) for r in plain_meds],
        "recovery": {
            "v": v,
            "kill_step": kill_step,
            "steps_lost": rec["steps_lost"],
            "recovery_ms": round(rec["recovery_s"] * 1e3, 1),
            "elastic_run_s": round(elastic_wall_s, 3),
            "bit_identical": True,
            "compile_once_per_chunk": True,
        },
    }


if __name__ == "__main__":
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()   # before the first compile; children inherit
    spec = json.loads(sys.argv[sys.argv.index("--one") + 1])
    print("RESULT " + json.dumps(run(spec)), flush=True)
