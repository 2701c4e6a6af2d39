"""95th percentile over the window's requests of first token at the client
minus the time the request was due (open loop) or sent (closed loop)."""
from perfbench import metrics_lib as ml, yardstick


def read(run):
    if run.get("kind") != "serve":
        return None
    t = ml.ttfts_ms(run)
    return yardstick.percentile(t, 95.0) if t else None
