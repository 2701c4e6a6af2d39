"""95th percentile over the window's requests of (last token - first
token) / (tokens - 1) at the client."""
from perfbench import metrics_lib as ml, yardstick


def read(run):
    if run.get("kind") != "serve":
        return None
    t = ml.tpots_ms(run)
    return yardstick.percentile(t, 95.0) if t else None
