#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. This process never opens JAX: the replica or the train worker
holds the chip. Earlier lines say what happened (stderr: the device, the
generator's lateness, requests sent / finished / failed, failed checks);
the last line of stdout is the result. With `--trace 0` the metrics are
the cell's end-to-end metrics, with `--trace 1` its per-layer metrics.

For the benchmark's own tests only: `--root DIR` reads BENCHMARK.json and
the files it names from another directory, and `--rehearse` lets the cell
run on the CPU at a tiny configuration; a rehearsal prints the device it
ran on and counts, and never a metric. `--sweep-rates a,b,c` (builder's
tool) runs one window per arrival rate on one replica and prints a row for
each, to find the knee; it prints no result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402
import uuid              # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--root", default=CHECKOUT)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--sweep-rates", type=lambda s: [
        float(x) for x in s.split(",")], default=None)
    args = p.parse_args(argv)
    args.root = os.path.abspath(args.root)
    args.out_dir = os.path.join(CHECKOUT, ".perfbench_out")
    return args


def _environment() -> None:
    """What the runtime's processes inherit: this checkout on their path
    (they import ray_tpu, and the benchmark's replica and train loop, by
    name), the compile cache at its fixed place in the checkout, the
    marker by which stragglers are found."""
    from perfbench.runtime import SESSION_ENV
    from ray_tpu._private.compile_cache import configure_compile_cache
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [CHECKOUT] + [p for p in os.environ.get("PYTHONPATH", "").split(
            os.pathsep) if p and p != CHECKOUT])
    os.environ[SESSION_ENV] = uuid.uuid4().hex
    print(f"compile cache: {configure_compile_cache()}", file=sys.stderr)


def _lateness_ms(run):
    from perfbench import yardstick
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.get("records", [])
            if r.get("due") is not None and r.get("sent") is not None]
    return yardstick.percentile(late, 95.0) if late else None


def main(argv=None) -> int:
    args = _parse(argv)
    sys.path.insert(0, CHECKOUT)
    from perfbench import spec, trace_reduce
    from perfbench.checks import Checks

    bench = spec.load_benchmark(args.root)
    cell = spec.workload(bench, args.workload)
    cfg = spec.load_config(bench, cell["config"], args.root)
    mix = spec.load_traffic(bench, cell["traffic"], args.root)
    _environment()
    checks = Checks()
    if mix["driver"] == "train":
        from perfbench import train_cell as driver
    else:
        from perfbench import serve_cell as driver
    run = driver.run(args, cell, cfg, mix, T_START, checks)
    if run.get("kind") == "sweep":
        return 0
    run.update(mix=mix, config=cfg, seconds=args.seconds, cell=cell["name"])
    device = run.get("device") or {}
    if args.trace and run.get("traced"):
        trace_dir = os.path.join(args.out_dir, "trace", cell["name"])
        run["trace"] = trace_reduce.reduce_dir(trace_dir)
        with open(os.path.join(trace_dir, "reduced.json"), "w") as f:
            json.dump(run["trace"], f)      # for a reader of PERF.md
        if not args.rehearse:       # the CPU's trace has no device plane
            checks.check(run["trace"].get("busy_s", 0) > 0,
                         "the trace shows no operation on the device")

    if run["kind"] == "serve":
        from perfbench import metrics_lib as ml
        attempted = len(ml.window_requests(mix, run))
        os.makedirs(args.out_dir, exist_ok=True)
        with open(os.path.join(args.out_dir, cell["name"]
                               + ".requests.json"), "w") as f:
            json.dump({"ttfts_ms": ml.ttfts_ms(run),     # per request, for
                       "tpots_ms": ml.tpots_ms(run)}, f)  # PERF.md's reader
        failed = ml.failed_count(run)
        late = _lateness_ms(run)
        print(f"requests: sent {attempted} in the window, "
              f"{attempted - failed} finished, {failed} failed; "
              f"{ml.in_flight_at(run, run['t_win1'])} in flight at the window's "
              f"end; "
              f"generator lateness p95 "
              f"{'n/a' if late is None else round(late, 3)} ms",
              file=sys.stderr)
        run["stalls"] = ml.stalls(run)
        print(f"stalls: {run['stalls']}", file=sys.stderr)
    else:
        attempted, failed = run["attempted"], run["failed"]
    print(f"device: {device.get('platform')} {device.get('kind')} "
          f"x{device.get('count')}", file=sys.stderr)

    out_device = {"platform": device.get("platform"),
                  "kind": device.get("kind"), "count": device.get("count"),
                  "memory_peak_bytes": device.get("memory_peak_bytes")}
    if args.rehearse:
        print(json.dumps({
            "rehearsal": True, "correct": not checks.failures,
            "attempted": attempted, "failed": failed,
            "device": out_device, "failures": checks.failures,
            "reference": run.get("reference")}))
        return 0 if not checks.failures else 1

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_of(bench, cell["name"], group):
        value = spec.load_reader(bench, m["name"], args.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": not checks.failures, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": out_device}
    if args.trace and run.get("trace"):
        out_device["busy_s"] = run["trace"]["busy_s"]
        out_device["window_s"] = run["traced"][1] - run["traced"][0]
        result["breakdown"], result["programs"] = trace_reduce.breakdown(
            run["trace"])
    result["reference"] = run.get("reference")
    if run.get("stalls"):
        result["stalls"] = run["stalls"]
    result["failures"] = checks.failures
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
