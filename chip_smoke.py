#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # one four-chip host: the sharded paths only

One chip (default). This process never opens a JAX backend: it talks to
the runtime in Python lists and JSON, and checks that about itself before
its last line. Through the public entry points only it runs

  serve: ray_tpu.init() -> serve.run(LLMDeployment("tpu-1b") on a replica
         actor leased `num_tpus=1`) -> streaming requests of a few, ~100 and
         ~1000 prompt tokens through the app handle, one through the HTTP
         proxy -> the replica's own report of the device it holds -> shut
         down and see that no process still has the chip open;
  train: JaxTrainer(ScalingConfig(num_workers=1, use_tpu=True)) whose loop
         builds make_train_fns for tpu-1b (B=8, L=1024, flash attention,
         adafactor) and takes five steps on one repeated batch.

Four chips (--chips 4, builder-run). One process drives all four: five
tpu-1b train steps on an fsdp=2 x tensor=2 mesh and one on the ring
attention mesh against the same steps on one device of the same host, then
a ShardedEngineReplica over default_serving_mesh() against the one-device
engine, token for token.

Every phase prints one JSON line. Times in them are smoke timings (one
cold run each, compile included where said), not metrics. The last stdout
line is {"ok": true, "device": {...}} only if every check of every phase
held; otherwise the exit code is not 0 and no such line is printed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import signal
import sys
import time
import uuid

# tpu-1b at the registry's full width and depth (d_model 2048, 16 layers,
# 16 heads of 128, d_ff 5632, vocab 32000), seeded weights, bf16 activations
SIZES = {
    "model": "tpu-1b",
    # serve
    "n_slots": 8, "max_len": 2048, "prefill_chunk": 128,
    "prefill_budget": 256, "prompt_lens": (5, 100, 1000),
    "max_new_tokens": 32,
    # train (tpu-1b at 8 x 1024 tokens a step)
    "train_batch": 8, "train_len": 1024, "train_steps": 5,
    "attention_impl": "flash",
    # --chips 4
    "sharded_max_len": 512, "sharded_prompt_lens": (5, 40, 100),
    "sharded_new_tokens": 32,
    # ray_tpu.init(resources=...): None = what the node detects
    "resources": None,
    # stop at the first failed phase: no 1B-parameter run on a CPU
    "stop_at_first_failure": True,
}
LOSS_TOLERANCE = 0.05     # |sharded - one device| per step, loss ~ ln(32000)
TIE_TOLERANCE = 0.05      # logit gap under which a greedy flip is a tie
SEED = 0


class Checks:
    """Every check of every phase lands here; any failure fails the run."""

    def __init__(self):
        self.failures = []

    def check(self, ok, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            print(f"CHECK FAILED: {what}", file=sys.stderr, flush=True)
        return bool(ok)


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _prompt(rng: random.Random, n: int, vocab: int):
    return [rng.randrange(1, vocab) for _ in range(n)]


def _wait_chip_released(checks: Checks, phase: str) -> None:
    from ray_tpu._private.accelerators.tpu import processes_holding_chips
    deadline = time.monotonic() + 60
    holders = processes_holding_chips()
    while holders and time.monotonic() < deadline:
        time.sleep(0.5)
        holders = processes_holding_chips()
    checks.check(not holders,
                 f"{phase}: processes {holders} still hold the chip after "
                 f"the runtime shut down")


def _wait_runtime_gone(checks: Checks, marker: str) -> None:
    """Every process the runtime started (they carry `marker` in their
    environment) is gone before the last line; stragglers are killed
    and fail the run."""
    from ray_tpu._private.proc_util import find_session_processes
    deadline = time.monotonic() + 30
    left = list(find_session_processes(marker))
    while left and time.monotonic() < deadline:
        time.sleep(0.5)
        left = list(find_session_processes(marker))
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    checks.check(not left, f"runtime processes {left} outlived "
                           f"ray_tpu.shutdown()")


def _vocab_of(model_name: str) -> int:
    from ray_tpu.models import MODEL_REGISTRY
    return MODEL_REGISTRY[model_name].vocab_size


# ------------------------------------------------------------------ serve
def serve_phase(sizes, checks: Checks, cache_dir: str) -> dict:
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.compile_cache import cache_entries
    from ray_tpu.inference import LLMDeployment

    t_phase = time.monotonic()
    entries0 = cache_entries()
    ray_tpu.init(resources=sizes["resources"])
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        if not checks.check(advertised >= 1,
                            f"serve: the node advertises TPU={advertised}; "
                            f"a replica with num_tpus=1 cannot be placed"):
            return {}
        app = serve.deployment(
            LLMDeployment, ray_actor_options={"num_tpus": 1}).bind(
            sizes["model"], n_slots=sizes["n_slots"],
            max_len=sizes["max_len"], prefill_chunk=sizes["prefill_chunk"],
            prefill_budget=sizes["prefill_budget"], seed=SEED)
        t0 = time.monotonic()
        serve.run(app, name="llm", _http=True, http_port=8137)
        h = serve.get_app_handle("llm")
        # first call returns once the replica actor has built its engine
        device = h.device_report.remote().result(timeout=900)
        setup_s = time.monotonic() - t0
        on_tpu = checks.check(
            device["platform"] == "tpu",
            f"serve: the replica's platform is {device['platform']!r}, "
            f"not 'tpu'")
        checks.check(device["count"] == advertised,
                     f"serve: the node advertises TPU={advertised} but the "
                     f"replica's JAX sees {device['count']} device(s)")
        if not on_tpu and sizes["stop_at_first_failure"]:
            return {"device": device}

        vocab = _vocab_of(sizes["model"])
        rng = random.Random(SEED)
        n_new = sizes["max_new_tokens"]
        stream = h.options(stream=True)
        requests = []
        outputs = {}
        for n in sizes["prompt_lens"]:
            prompt = _prompt(rng, n, vocab)
            t0 = time.monotonic()
            toks = list(stream.remote(prompt, max_new_tokens=n_new))
            requests.append({"via": "handle", "prompt_tokens": n,
                             "tokens": len(toks),
                             "wall_s": round(time.monotonic() - t0, 3)})
            checks.check(
                len(toks) == n_new and all(
                    isinstance(t, int) and 0 <= t < vocab for t in toks),
                f"serve: prompt of {n} tokens returned {len(toks)} tokens, "
                f"wanted {n_new} ids below {vocab}")
            outputs[n] = (prompt, toks)
        # equal greedy requests give equal tokens (the second one is
        # served from the prefix cache the first one filled)
        n_mid = sizes["prompt_lens"][1]
        prompt, first = outputs[n_mid]
        again = list(stream.remote(prompt, max_new_tokens=n_new))
        checks.check(again == first,
                     f"serve: the same greedy request gave other tokens "
                     f"the second time: {first[:8]} vs {again[:8]}")
        # one request through the HTTP proxy (NDJSON stream, the
        # deployment's default max_new_tokens of 64)
        addr = next(iter(serve.proxies().values()))["http"]
        req = urllib.request.Request(
            f"http://{addr}/", data=json.dumps(prompt).encode(),
            headers={"Content-Type": "application/json",
                     "X-RayTPU-Stream": "1"})
        t0 = time.monotonic()
        with urllib.request.urlopen(req, timeout=600) as resp:
            via_http = [json.loads(ln)
                        for ln in resp.read().decode().splitlines()]
        requests.append({"via": "http", "prompt_tokens": n_mid,
                         "tokens": len(via_http),
                         "wall_s": round(time.monotonic() - t0, 3)})
        checks.check(len(via_http) == 64 and via_http[:n_new] == first,
                     f"serve: the HTTP proxy streamed {len(via_http)} "
                     f"tokens starting {via_http[:8]}; wanted 64 starting "
                     f"{first[:8]}")
        stats = h.stats.remote().result(timeout=60)
        checks.check(stats["decode_compile_count"] == 1,
                     f"serve: decode_compile_count is "
                     f"{stats['decode_compile_count']}, not 1")
        device = h.device_report.remote().result(timeout=60)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    _wait_chip_released(checks, "serve")
    out = {"phase": "serve", "model": sizes["model"],
           "entry": "serve.run(LLMDeployment, num_tpus=1) + handle + HTTP",
           "n_slots": sizes["n_slots"], "max_len": sizes["max_len"],
           "device": device, "node_advertised_tpus": advertised,
           "requests": requests,
           "decode_compile_count": stats["decode_compile_count"],
           "tokens_generated": stats["tokens_generated"],
           "setup_s_replica_start_smoke_timing": round(setup_s, 1),
           "wall_s_smoke_timing": round(time.monotonic() - t_phase, 1),
           "compile_cache": {"dir": cache_dir, "entries_before": entries0,
                             "entries_after": cache_entries()}}
    emit(out)
    return out


# ------------------------------------------------------------------ train
def _train_loop(config):
    """Runs inside the train worker, the process leased the chip."""
    import jax
    import optax

    from ray_tpu import train
    from ray_tpu.models import MODEL_REGISTRY, TransformerLM
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_fns
    from ray_tpu.util.profiling import device_report

    cfg = dataclasses.replace(MODEL_REGISTRY[config["model"]],
                              attention_impl=config["attention_impl"])
    B, L = config["train_batch"], config["train_len"]
    mesh = make_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    init_fn, step_fn, _ = make_train_fns(
        TransformerLM(cfg), optax.adafactor(1e-3), mesh,
        batch_shape=(B, L + 1))
    t0 = time.monotonic()
    state = init_fn(jax.random.PRNGKey(config["seed"]))
    tokens = jax.random.randint(jax.random.PRNGKey(config["seed"] + 1),
                                (B, L + 1), 0, cfg.vocab_size)
    has_kernel = "tpu_custom_call" in step_fn.lower(state, tokens).as_text()
    for step in range(config["train_steps"]):
        state, metrics = step_fn(state, tokens)
        loss = float(metrics["loss"])       # blocks on the step
        report = {"step": step + 1, "loss": loss,
                  "wall_s": round(time.monotonic() - t0, 3)}
        if step == 0:
            report["pallas_call_in_step"] = has_kernel
        if step == config["train_steps"] - 1:
            report["device"] = device_report()
        train.report(report)
        t0 = time.monotonic()


def train_phase(sizes, checks: Checks, cache_dir: str) -> dict:
    import math

    import ray_tpu
    from ray_tpu._private.compile_cache import cache_entries
    from ray_tpu.train import JaxTrainer, ScalingConfig

    t_phase = time.monotonic()
    entries0 = cache_entries()
    ray_tpu.init(resources=sizes["resources"])
    try:
        result = JaxTrainer(
            _train_loop,
            train_loop_config={k: sizes[k] for k in (
                "model", "attention_impl", "train_batch", "train_len",
                "train_steps")} | {"seed": SEED},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        ).fit()
    finally:
        ray_tpu.shutdown()
    _wait_chip_released(checks, "train")
    if result.error is not None:
        raise result.error
    history = [m for m in result.metrics_history if "loss" in m]
    losses = [m["loss"] for m in history]
    checks.check(len(losses) == sizes["train_steps"],
                 f"train: {len(losses)} steps reported, wanted "
                 f"{sizes['train_steps']}")
    checks.check(all(math.isfinite(x) for x in losses),
                 f"train: a loss is not finite: {losses}")
    checks.check(len(losses) > 1 and losses[-1] < losses[0],
                 f"train: the loss did not fall on a repeated batch: "
                 f"{losses}")
    checks.check(history and history[0].get("pallas_call_in_step"),
                 "train: the lowered step holds no tpu_custom_call: the "
                 "Pallas flash kernel is not in the program")
    device = (history[-1].get("device") or {}) if history else {}
    checks.check(device.get("platform") == "tpu",
                 f"train: the worker's platform is "
                 f"{device.get('platform')!r}, not 'tpu'")
    out = {"phase": "train", "model": sizes["model"],
           "entry": "JaxTrainer(ScalingConfig(num_workers=1, use_tpu=True))"
                    " + make_train_fns",
           "batch": sizes["train_batch"], "seq_len": sizes["train_len"],
           "attention_impl": sizes["attention_impl"],
           "optimizer": "adafactor", "device": device, "losses": losses,
           "pallas_call_in_step": bool(
               history and history[0].get("pallas_call_in_step")),
           "first_step_s_with_compile_smoke_timing":
               history[0]["wall_s"] if history else None,
           "later_step_s_smoke_timing":
               [m["wall_s"] for m in history[1:]],
           "wall_s_smoke_timing": round(time.monotonic() - t_phase, 1),
           "compile_cache": {"dir": cache_dir, "entries_before": entries0,
                             "entries_after": cache_entries()}}
    emit(out)
    return out


def one_chip(sizes, checks: Checks) -> dict:
    from ray_tpu._private.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()     # raylet and workers inherit
    served = serve_phase(sizes, checks, cache_dir)
    if checks.failures and sizes["stop_at_first_failure"]:
        return {}
    trained = train_phase(sizes, checks, cache_dir)
    # the marker proc_util's hygiene scan looks for, set for this run
    # alone: every daemon and worker started above inherited it
    _wait_runtime_gone(checks, os.environ["RAY_TPU_TEST_SESSION"])
    xb = sys.modules.get("jax._src.xla_bridge")
    checks.check(not (xb and xb.backends_are_initialized()),
                 "this (parent) process initialised a JAX backend")
    a, b = served.get("device", {}), trained.get("device", {})
    checks.check(
        (a.get("platform"), a.get("kind"), a.get("count"))
        == (b.get("platform"), b.get("kind"), b.get("count")),
        f"serve and train report different devices: {a} vs {b}")
    return {"platform": a.get("platform"), "kind": a.get("kind"),
            "count": a.get("count")}


# ---------------------------------------------------------------- 4 chips
def _placement(tree) -> dict:
    """Where a sharded tree sits: per-device bytes in use, and how many
    of its leaves are split (no device holds the whole array)."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    split = sum(1 for x in leaves if any(
        s.data.shape != x.shape for s in x.addressable_shards))
    return {"bytes_in_use_per_device": [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()],
            "leaves": len(leaves), "leaves_split_over_devices": split,
            "tree_bytes": int(sum(x.nbytes for x in leaves))}


def _check_spread(checks: Checks, what: str, placement: dict) -> None:
    per_dev = placement["bytes_in_use_per_device"]
    checks.check(
        placement["leaves_split_over_devices"] > 0
        and all(b and b < placement["tree_bytes"] for b in per_dev),
        f"{what}: the parameters are not spread over the chips "
        f"({placement})")


def four_chip_train(sizes, checks: Checks) -> dict:
    import jax
    import optax

    from __graft_entry__ import _StrictCompileStderr, _mesh_for
    from ray_tpu.models import MODEL_REGISTRY, TransformerLM
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_fns

    B, L = sizes["train_batch"], sizes["train_len"]
    base = MODEL_REGISTRY[sizes["model"]]
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (B, L + 1), 0,
                                base.vocab_size)

    def run(mesh_cfg, devices, impl, steps):
        cfg = dataclasses.replace(base, attention_impl=impl)
        mesh = make_mesh(mesh_cfg, devices=devices)
        init_fn, step_fn, _ = make_train_fns(
            TransformerLM(cfg), optax.adafactor(1e-3), mesh,
            batch_shape=(B, L + 1))
        t0 = time.monotonic()
        state = init_fn(jax.random.PRNGKey(SEED))
        jax.block_until_ready(state)
        placement = _placement(state.params)
        text = step_fn.lower(state, tokens).as_text()
        losses, step_s = [], []
        for _ in range(steps):
            t_step = time.monotonic()
            state, metrics = step_fn(state, tokens)
            losses.append(float(metrics["loss"]))     # blocks on the step
            step_s.append(round(time.monotonic() - t_step, 3))
        return {"mesh": {k: v for k, v in mesh.shape.items() if v > 1},
                "attention_impl": impl, "losses": losses,
                "step_s_smoke_timing_first_with_compile": step_s,
                "pallas_call_in_step": "tpu_custom_call" in text,
                "placement": placement,
                "wall_s_with_compile_smoke_timing":
                    round(time.monotonic() - t0, 1)}

    devices = jax.devices()
    steps, impl = sizes["train_steps"], sizes["attention_impl"]
    # the SPMD partitioner's "involuntary full rematerialization" (a
    # silent per-step all-gather) fails the run, as in dryrun_multichip
    with _StrictCompileStderr():
        sharded = run(MeshConfig(fsdp=2, tensor=2), devices, impl, steps)
        ring = run(_mesh_for(4), devices, "auto", 1)
    single = run(MeshConfig(data=1, fsdp=1), devices[:1], impl, steps)
    _check_spread(checks, "train fsdp=2 x tensor=2", sharded["placement"])
    checks.check(sharded["pallas_call_in_step"],
                 "train fsdp=2 x tensor=2: no tpu_custom_call in the step")
    diffs = [abs(a - b) for a, b in zip(sharded["losses"], single["losses"])]
    checks.check(len(diffs) == steps and max(diffs) <= LOSS_TOLERANCE,
                 f"train: sharded losses {sharded['losses']} differ from "
                 f"one device's {single['losses']} by more than "
                 f"{LOSS_TOLERANCE}")
    checks.check(abs(ring["losses"][0] - single["losses"][0])
                 <= LOSS_TOLERANCE,
                 f"train: the ring-attention mesh's first loss "
                 f"{ring['losses'][0]} differs from one device's "
                 f"{single['losses'][0]} by more than {LOSS_TOLERANCE}")
    checks.check(sharded["losses"][-1] < sharded["losses"][0],
                 f"train: the sharded loss did not fall: {sharded['losses']}")
    out = {"phase": "train_4chips", "model": sizes["model"], "batch": B,
           "seq_len": L, "loss_tolerance": LOSS_TOLERANCE,
           "max_abs_loss_diff": max(diffs) if diffs else None,
           "fsdp2_tensor2": sharded, "ring_mesh": ring,
           "one_device": single}
    emit(out)
    return out


def _is_tie(model, params, context, tok_a: int, tok_b: int) -> float:
    """Gap between two candidate next tokens' logits under the one-device
    model, teacher-forced on `context` (plain forward pass)."""
    import jax.numpy as jnp
    logits = model.apply({"params": params},
                         jnp.asarray([context], jnp.int32))[0, -1]
    return abs(float(logits[tok_a]) - float(logits[tok_b]))


def four_chip_serve(sizes, checks: Checks) -> dict:
    import jax

    from ray_tpu.inference import LLMDeployment
    from ray_tpu.serve.sharded import (ShardedEngineReplica,
                                       default_serving_mesh)

    knobs = dict(n_slots=sizes["n_slots"], max_len=sizes["sharded_max_len"],
                 prefill_chunk=sizes["prefill_chunk"],
                 prefill_budget=sizes["prefill_budget"], seed=SEED)
    t0 = time.monotonic()
    mesh = default_serving_mesh()
    sharded = ShardedEngineReplica(sizes["model"], mesh=mesh, **knobs)
    jax.block_until_ready(sharded.engine.params)
    placement = _placement(sharded.engine.params)
    _check_spread(checks, "sharded serving", placement)
    single = LLMDeployment(sizes["model"], **knobs)
    vocab = _vocab_of(sizes["model"])
    rng = random.Random(SEED)
    n_new = sizes["sharded_new_tokens"]
    compared = []
    for n in sizes["sharded_prompt_lens"]:
        prompt = _prompt(rng, n, vocab)
        got = sharded.generate(prompt, max_new_tokens=n_new)
        want = single.generate(prompt, max_new_tokens=n_new)
        same = next((i for i, (a, b) in enumerate(zip(got, want))
                     if a != b), min(len(got), len(want)))
        row = {"prompt_tokens": n, "tokens": len(got),
               "equal_prefix": same}
        ok = len(got) == len(want) == n_new
        if ok and same < n_new:
            # greedy argmax over near-equal bf16 logits may flip between
            # two reduction orders; anything but such a tie is a fault,
            # and after a tie the two continuations are not comparable
            row["logit_gap_at_divergence"] = _is_tie(
                single.model, single.engine.params, prompt + want[:same],
                got[same], want[same])
            ok = row["logit_gap_at_divergence"] <= TIE_TOLERANCE
        checks.check(ok, f"sharded serving: prompt of {n} tokens: {row}; "
                         f"sharded {got[:8]}.. one device {want[:8]}..")
        compared.append(row)
    st = sharded.stats()
    checks.check(st["decode_compile_count"] == 1,
                 f"sharded serving: decode_compile_count is "
                 f"{st['decode_compile_count']}, not 1")
    single.engine.stop()
    out = {"phase": "serve_4chips", "model": sizes["model"],
           "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
           "n_slots": knobs["n_slots"], "max_len": knobs["max_len"],
           "placement": placement, "compared": compared,
           "tie_tolerance": TIE_TOLERANCE,
           "decode_compile_count": st["decode_compile_count"],
           "wall_s_with_compile_smoke_timing":
               round(time.monotonic() - t0, 1)}
    emit(out)
    return out


def four_chips(sizes, checks: Checks) -> dict:
    from ray_tpu._private.compile_cache import (cache_entries,
                                                configure_compile_cache)
    cache_dir = configure_compile_cache()
    entries0 = cache_entries()
    import jax

    from ray_tpu._private.accelerators import detect_node_accelerators
    from ray_tpu.util.profiling import device_report

    device = device_report()
    if not checks.check(device["platform"] == "tpu" and device["count"] == 4,
                        f"--chips 4 needs four TPU chips in this process; "
                        f"JAX reports {device}"):
        return {}
    advertised = detect_node_accelerators().get("TPU", 0)
    checks.check(advertised == device["count"],
                 f"the node would advertise TPU={advertised} but JAX sees "
                 f"{device['count']} devices")
    four_chip_train(sizes, checks)
    four_chip_serve(sizes, checks)
    device = device_report()
    emit({"phase": "summary_4chips", "device": device,
          "peak_bytes_in_use_per_device": [
              (d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()],
          "compile_cache": {"dir": cache_dir, "entries_before": entries0,
                            "entries_after": cache_entries()}})
    return {"platform": device["platform"], "kind": device["kind"],
            "count": device["count"]}


def main(argv=None, _test_sizes=None) -> int:
    """_test_sizes: tier-1's CPU rehearsal of this control flow passes a
    dict of SIZES overrides (a debug model, short prompts, a fake TPU
    resource). Nothing else shrinks the run — no environment variable."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    # the runtime's processes (gcs, raylet, workers) import ray_tpu — and
    # the train worker this file — from the checkout this file sits in
    here = os.path.dirname(os.path.abspath(__file__))
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [here] + [p for p in os.environ.get("PYTHONPATH", "").split(
            os.pathsep) if p and p != here])
    os.environ["RAY_TPU_TEST_SESSION"] = uuid.uuid4().hex
    sizes = SIZES if _test_sizes is None else {**SIZES, **_test_sizes}
    checks = Checks()
    device = (one_chip if args.chips == 1 else four_chips)(sizes, checks)
    checks.check(device.get("platform") == "tpu"
                 and device.get("count") == args.chips,
                 f"ran on {device}, wanted {args.chips} TPU chip(s)")
    if checks.failures:
        print(f"chip_smoke: {len(checks.failures)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
