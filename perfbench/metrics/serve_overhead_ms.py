"""Serve plane: median over requests of the client's TTFT from the send
minus the replica's own time from `__call__` entry to its first yield.
Both processes are on one host and time.monotonic() is one clock."""
from perfbench import metrics_lib as ml, yardstick


def read(run):
    timings = run.get("replica_timings")
    if not timings:
        return None
    out = []
    for r in ml.window_requests(run["mix"], run):
        t = timings.get(r["idx"])
        if t is None or not ml.finished(r):
            continue
        out.append(((r["arrivals"][0] - r["sent"]) - (t[1] - t[0])) * 1e3)
    return yardstick.median(out) if out else None
