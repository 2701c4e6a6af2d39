"""Tokens of the window's training steps over the window's seconds, timed
in the train worker around float(metrics["loss"]); the window ends with
the step that crosses --seconds."""


def read(run):
    if run.get("kind") != "train":
        return None
    return run["window_steps"] * run["tokens_per_step"] / run["window_s"]
