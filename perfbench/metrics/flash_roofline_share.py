"""Kernels, training: causal attention FLOPs forward and backward of the
traced steps over the chip's peak, over the summed device time of the
Pallas flash kernels in the trace. The kernels carry no `name=` yet: XLA
names their ops `attn.<n>` today, after the flax module that calls them
(nothing else in the step is named so: fusions are `fusion.<n>`), forward,
the forward recomputed under remat, and the two backward kernels alike.
The time therefore holds the recomputed forward, whose FLOPs do not count,
so the share understates the kernels. Where no such op is found the metric
is left out (PERF.md, list for `tracing`)."""
import re

from perfbench import spec, yardstick

KERNEL = re.compile(r"^attn\.\d+( |$)")


def read(run):
    tr = run.get("trace") or {}
    steps = (tr.get("programs", {}).get(run.get("step_program", ""), {})
             .get("count"))
    t = sum(v for k, v in tr.get("op_self_s", {}).items()
            if KERNEL.search(k))
    if not steps or t <= 0:
        return None
    cfg = run["config"]
    flops = steps * spec.family_of(cfg).causal_attention_flops(
        cfg, run["mix"]["batch"], run["mix"]["seq_len"], backward=True)
    peak = yardstick.peaks(run["device"]["kind"])["flops_per_s"]
    return flops / peak / t * 100.0
