"""A training cell: `JaxTrainer` with one worker on the chip, whose loop
(`train_loop`, below, the benchmark's own function, grown from
chip_smoke.py's _train_loop) builds `make_train_fns` and steps on fresh
seeded batches. The loop travels to the worker by name: the benchmark puts
its checkout on the worker's PYTHONPATH.

In the worker: seeded state from the program's own `init_fn`, the plain
reference's loss on the first batch (before any update), two warm-up steps
(the first compiles), then the window: steps until `seconds` have passed,
each timed around float(metrics["loss"]), `train.report` every step. The
window ends with the step that crosses `seconds`, so the rate is all of its
steps over all of its time, and not a count of whole steps in a fixed time
(at two steps a second that count moves by a whole per cent at once).
"""

from __future__ import annotations

import math
import os
import shutil
import time

from perfbench import spec
from perfbench.checks import Checks

WARM_STEPS = 2


def train_loop(config):
    """Runs inside the train worker, the process leased the chip."""
    import jax
    import optax

    from perfbench import trace_reduce, traffic
    from ray_tpu import train
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_fns

    cfg, mix = config["config"], config["mix"]
    family = spec.family_of(cfg)
    B, L = mix["batch"], mix["seq_len"]
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d)}
    if device["platform"] != "tpu" and not config["rehearse"]:
        raise RuntimeError(f"the train worker runs on {device}: no chip")
    mesh = make_mesh(MeshConfig(data=1, fsdp=1), devices=d[:1])
    opt = {"adafactor": optax.adafactor}[cfg["train"]["optimizer"]](
        cfg["train"]["learning_rate"])
    init_fn, step_fn, _ = make_train_fns(
        family.build_model(config["model_kwargs"]), opt, mesh,
        batch_shape=(B, L + 1), loss_chunk=cfg["train"].get("loss_chunk"))
    state = init_fn(jax.random.PRNGKey(config["seed"] % (2 ** 31)))

    def batch(step):
        return traffic.train_batch(mix, config["seed"], step,
                                   cfg["vocab_size"])

    first = batch(0)
    lowered = step_fn.lower(state, first).as_text()
    from flax.core import meta
    ref_loss = family.batch_loss(meta.unbox(state.params), cfg, first)
    losses = []
    for step in range(WARM_STEPS):
        state, metrics = step_fn(state, batch(step))
        losses.append(float(metrics["loss"]))
    report = {"phase": "setup", "device": device, "ref_loss": ref_loss,
              "first_loss": losses[0], "warm_losses": losses,
              "pallas_call_in_step": "tpu_custom_call" in lowered}
    train.report(report)

    seconds, trace_dir = config["seconds"], config["trace_dir"]
    trace_steps = int(mix.get("trace_steps", 3)) if trace_dir else 0
    t_win0 = time.monotonic()
    ends, step = [], WARM_STEPS
    while True:
        state, metrics = step_fn(state, batch(step))
        loss = float(metrics["loss"])            # blocks on the step
        t = time.monotonic()
        ends.append(t)
        step += 1
        train.report({"phase": "window", "step": step, "loss": loss,
                      "t_end": t})
        if t - t_win0 >= seconds:
            break
    traced = None
    if trace_dir:
        # the traced slice follows the window: a few more steps
        trace_reduce.start(trace_dir)
        t_a = time.monotonic()
        for _ in range(trace_steps):
            state, metrics = step_fn(state, batch(step))
            float(metrics["loss"])
            step += 1
        t_b = time.monotonic()
        trace_reduce.stop()
        traced = (t_a, t_b)
    device["memory_peak_bytes"] = max(
        (x.memory_stats() or {}).get("peak_bytes_in_use", 0) for x in d)
    train.report({"phase": "end", "t_win0": t_win0, "step_ends": ends,
                  "traced": traced, "device": device})


def run(args, cell, cfg, mix, t_start, checks: Checks) -> dict:
    import ray_tpu
    from perfbench.runtime import shutdown_and_verify
    from ray_tpu.train import JaxTrainer, ScalingConfig

    model_kwargs = spec.family_of(cfg).model_kwargs(cfg)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(args.out_dir, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    ray_tpu.init(resources={"TPU": cell["chips"]} if args.rehearse else None)
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        if advertised < cell["chips"]:
            raise SystemExit(f"the node advertises TPU={advertised}; the "
                             f"cell needs {cell['chips']}")
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "config": {k: v for k, v in cfg.items() if k != "_entry"},
                "mix": mix, "model_kwargs": model_kwargs,
                "seed": args.seed, "seconds": args.seconds,
                "trace_dir": trace_dir, "rehearse": args.rehearse},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        ).fit()
    finally:
        shutdown_and_verify(checks, serve=False)
    if result.error is not None:
        raise result.error
    by_phase = {}
    for m in result.metrics_history or []:
        by_phase.setdefault(m.get("phase"), []).append(m)
    setup, end = by_phase.get("setup", [{}])[0], by_phase.get("end", [{}])[0]
    window = by_phase.get("window", [])
    if not checks.check(setup and end and window,
                        "the train worker reported no set-up, window or "
                        "end"):
        return {"kind": "train", "attempted": 0, "failed": 1}
    losses = setup["warm_losses"] + [m["loss"] for m in window]
    checks.check(all(math.isfinite(x) for x in losses),
                 f"a loss is not finite: {losses}")
    tol = cfg["reference_tolerance"]["loss"]
    checks.check(abs(setup["first_loss"] - setup["ref_loss"]) <= tol,
                 f"the first step's loss {setup['first_loss']} differs from "
                 f"the reference's {setup['ref_loss']} by more than {tol}")
    if not args.rehearse:
        checks.check(setup["pallas_call_in_step"],
                     "the lowered step holds no tpu_custom_call")
    return {"kind": "train", "device": end["device"],
            "setup_s": end["t_win0"] - t_start,
            "window_steps": len(end["step_ends"]),
            "window_s": end["step_ends"][-1] - end["t_win0"],
            "tokens_per_step": mix["batch"] * mix["seq_len"],
            "step_ends": end["step_ends"], "t_win0": end["t_win0"],
            "traced": end["traced"], "step_program": "jit_step_fn",
            "reference": {"loss": setup["ref_loss"],
                          "first_loss": setup["first_loss"],
                          "tolerance": tol},
            "attempted": len(window), "failed": 0}
