"""Kernels, serving: the least time the sliding-window layers' attention
over the traced tiles could take on this chip (the larger of its FLOPs over
the peak and its bytes over the peak bandwidth: QK^T and AV over the pairs
inside the window, K and V of the positions a tile attends once, by the
family's `win_attend_flops` / `win_attend_bytes`) over the device time
inside the `win_attend` scope of the tile program's executions in the
trace. A tile counts its mean real rows over the counters' window, at the
mean over the window's prompts of what a prompt's tiles attend; the decode
rows that ride in the tile's program run under `win_row` and are not
counted. None where the trace, the scope or the family's count is absent."""
from perfbench import metrics_lib as ml, scope_times, spec, yardstick

SCOPE, PROGRAM = "win_attend", "jit_prefill"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "win_attend_flops"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    rows = scope_times.tile_tokens(run)
    prompts = [r["prompt_len"] for r in ml.window_requests(run["mix"], run)]
    if got is None or not rows or not prompts:
        return None
    seconds, runs = got
    W = cfg["sliding_window"]
    # a prompt's rows attend window_pairs(0, p) pairs however it is tiled
    pairs = rows * sum(family.window_pairs(cfg, 0, p)
                       for p in prompts) / sum(prompts)
    starts = [at for p in prompts for at in range(0, p, max(1, int(rows)))]
    keys = sum(min(at + rows, rows + W - 1) for at in starts) / len(starts)
    peaks = yardstick.peaks(run["device"]["kind"])
    floor_s = runs * max(
        family.win_attend_flops(cfg, pairs) / peaks["flops_per_s"],
        family.win_attend_bytes(cfg, rows, keys, 2.0) / peaks["bytes_per_s"])
    return floor_s / seconds * 100.0
