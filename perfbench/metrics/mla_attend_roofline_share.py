"""Kernels, serving: the least time the latent attention over the traced
tiles could take on this chip (the larger of its FLOPs over the peak and its
bytes over the peak bandwidth, by the family's `mla_attend_flops` /
`mla_attend_bytes`: q.k at 192 and p.v at 128 a head over the causal pairs
and the up-projection of the tile's OWN rows; the latents of the positions a
tile attends, once; the same work whatever form implements it) over the
device time inside the `mla_attend` scope of the tile program's executions
in the trace, as metrics/win_attend_roofline_share.py counts its tiles: a
tile counts its mean real rows over the counters' window, at the mean over
the window's prompts of what a prompt's tiles attend; the decode rows that
ride in the tile's program run under `mla_row` and are not counted. None
where the trace, the scope or the family's count is absent."""
from perfbench import metrics_lib as ml, scope_times, spec, yardstick

SCOPE, PROGRAM = "mla_attend", "jit_prefill"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "mla_attend_flops"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    rows = scope_times.tile_tokens(run)
    prompts = [r["prompt_len"] for r in ml.window_requests(run["mix"], run)]
    if got is None or not rows or not prompts:
        return None
    seconds, runs = got
    # a prompt's rows attend causal_pairs(0, p) pairs however it is tiled
    pairs = rows * sum(family.causal_pairs(0, p)
                       for p in prompts) / sum(prompts)
    starts = [at for p in prompts for at in range(0, p, max(1, int(rows)))]
    keys = sum(at + rows for at in starts) / len(starts)
    peaks = yardstick.peaks(run["device"]["kind"])
    floor_s = runs * max(
        family.mla_attend_flops(cfg, pairs, rows) / peaks["flops_per_s"],
        family.mla_attend_bytes(cfg, keys, 2.0) / peaks["bytes_per_s"])
    return floor_s / seconds * 100.0
