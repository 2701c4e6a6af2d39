"""Kernels, serving: the least time the block-sparse layers' attention over
the traced tiles could take on this chip (the larger of its FLOPs over the
peak and its bytes over the peak bandwidth: QK^T and AV over the positions
each query SELECTED, by the family's `blk_attend_flops` /
`blk_attend_bytes`) over the device time inside the `blk_attend` scope of
the tile program's executions in the trace. A tile counts its mean real
tokens over the counters' window, and a token the mean over the window's
prompts of the positions a prompt's tokens select; the decode rows that
ride in the tile's program run under the same scope and their (small) work
is left out, so the share reads low, never high. None where the trace or
the scope is absent."""
from perfbench import metrics_lib as ml, scope_times, spec, yardstick

SCOPE, PROGRAM = "blk_attend", "jit_prefill"


def read(run):
    if not run.get("traced"):
        return None
    cfg = run["config"]
    family = spec.family_of(cfg)
    if not hasattr(family, "blk_attend_flops"):
        return None
    got = scope_times.scope_seconds(run, SCOPE, PROGRAM)
    tokens = scope_times.tile_tokens(run)
    prompts = [r["prompt_len"] for r in ml.window_requests(run["mix"], run)]
    if got is None or not tokens or not prompts:
        return None
    seconds, runs = got
    cap = family.selected_positions(cfg, float("inf"))
    # sum over a prompt's tokens of min(t + 1, cap), in closed form
    pairs = sum(min(p, cap) * (min(p, cap) + 1) / 2.0 + max(p - cap, 0) * cap
                for p in prompts) / sum(prompts)
    live = sum(prompts) / len(prompts) / 2.0       # a tile's mean context
    layers = sum(k == "minicpm4" for k in cfg["mixer_types"])
    peaks = yardstick.peaks(run["device"]["kind"])
    floor_s = runs * layers * max(
        family.blk_attend_flops(cfg, tokens * pairs) / peaks["flops_per_s"],
        family.blk_attend_bytes(cfg, tokens, min(live, cap), 2.0)
        / peaks["bytes_per_s"])
    return floor_s / seconds * 100.0
