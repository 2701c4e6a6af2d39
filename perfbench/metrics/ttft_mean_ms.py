"""Mean over the window's requests of first token at the client minus the
time the request was due (open loop) or sent (closed loop). The tail
(ttft_p95_ms) rests on a handful of requests, each of which lands a whole
engine step earlier or later from run to run; the mean is over all."""
import statistics

from perfbench import metrics_lib as ml


def read(run):
    if run.get("kind") != "serve":
        return None
    t = ml.ttfts_ms(run)
    return statistics.fmean(t) if t else None
