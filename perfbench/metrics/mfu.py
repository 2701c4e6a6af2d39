"""Train step: useful FLOPs of a step (the family's train_step_flops;
recomputed operations do not count) times steps per second of the window,
over the chip's peak."""
from perfbench import spec, yardstick


def read(run):
    if run.get("kind") != "train" or not run.get("window_steps"):
        return None
    cfg = run["config"]
    flops = spec.family_of(cfg).train_step_flops(
        cfg, run["mix"]["batch"], run["mix"]["seq_len"])
    peak = yardstick.peaks(run["device"]["kind"])["flops_per_s"]
    return flops * run["window_steps"] / run["window_s"] / peak * 100.0
