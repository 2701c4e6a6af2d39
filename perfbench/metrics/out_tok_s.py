"""Output tokens that reached clients inside the window, over its seconds."""
from perfbench import metrics_lib as ml


def read(run):
    if run.get("kind") != "serve":
        return None
    a, b = run["t_win0"], run["t_win1"]
    return ml.tokens_between(run, a, b) / (b - a)
