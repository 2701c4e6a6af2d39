"""Kernels, serving: the least time the differential attention over the
traced tiles could take on this chip (the larger of its FLOPs over the peak
and its bytes over the peak bandwidth: for each of the query heads a score
product of head_dim and a value product of 2 head_dim over the pairs a
tile's rows attend, inside the window in a window layer and every earlier
position in the one full layer; K and V of the positions a tile attends
once a layer; by the family's `diff_attend_flops` / `diff_attend_bytes` /
`window_pairs`) over the device time inside the `diff_attend` scope of the
tile program's executions in the trace. A tile counts its mean real rows
over the counters' window, at the mean over the window's prompts of what a
prompt's tiles attend. The cross layers' one sampled row a tile (it attends
every earlier position of the one cache, once a cross layer) is counted
too; the decode rows that ride in the tile's program run under `diff_row`
and are not. None where the trace, the scope or the family's count is
absent."""
from perfbench import metrics_lib as ml, scope_times, spec, yardstick

SCOPE, PROGRAM = "diff_attend", "jit_prefill"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "diff_attend_flops"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    rows = scope_times.tile_tokens(run)
    prompts = [r["prompt_len"] for r in ml.window_requests(run["mix"], run)]
    if got is None or not rows or not prompts:
        return None
    seconds, runs = got
    W = cfg["sliding_window"]
    n = family._layers(cfg)
    # a prompt's rows attend these pairs however it is tiled; a tile is
    # `rows` of a prompt's rows
    share = rows / sum(prompts)
    win_pairs = share * sum(family.window_pairs(cfg, 0, p) for p in prompts)
    full_pairs = share * sum(p * (p + 1) / 2.0 for p in prompts)
    starts = [at for p in prompts for at in range(0, p, max(1, int(rows)))]
    ends = sum(at + rows for at in starts) / len(starts)
    win_keys = sum(min(at + rows, rows + W - 1) for at in starts) \
        / len(starts)
    pairs = n["win"] * win_pairs + n["att"] * full_pairs + n["xat"] * ends
    keys = n["win"] * win_keys + (n["att"] + n["xat"]) * ends
    peaks = yardstick.peaks(run["device"]["kind"])
    floor_s = runs * max(
        family.diff_attend_flops(cfg, pairs) / peaks["flops_per_s"],
        family.diff_attend_bytes(cfg, keys, 2.0) / peaks["bytes_per_s"])
    return floor_s / seconds * 100.0
