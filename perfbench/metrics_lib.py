"""What the metric readers share: the reduction from a run's records
(client clocks, engine counters, the reduced trace) to numbers.

A reader is `read(run) -> number | None` in `metrics/<name>.py`; `run` is
the dict the cell's driver returns. A reader that finds nothing to read
returns None and the metric is left out of the line.
"""

from __future__ import annotations

from perfbench import yardstick


def window_requests(mix, win) -> list:
    """The requests the tails are taken over: due in the window (open
    loop) or sent in it (closed loop)."""
    key = "due" if mix["driver"] == "open" else "sent"
    return [r for r in win["records"] if r[key] is not None
            and win["t_win0"] <= r[key] < win["t_win1"]]


def _ref_time(run, r):
    return r["due"] if run["mix"]["driver"] == "open" else r["sent"]


def finished(r) -> bool:
    return r["done"] and r["error"] is None


def failed_count(run) -> int:
    return sum(1 for r in window_requests(run["mix"], run)
               if not finished(r))


def ttfts_ms(run) -> list:
    """First token at the client minus when the request was due (open
    loop) or sent (closed loop); a request that failed or never got a
    token enters at the drain limit."""
    out = []
    for r in window_requests(run["mix"], run):
        first = r["arrivals"][0] if r["arrivals"] and finished(r) \
            else run["deadline"]
        out.append((first - _ref_time(run, r)) * 1e3)
    return out


def tpots_ms(run) -> list:
    """Per request: (last token - first token) / (tokens - 1) at the
    client. Per request and not per gap: the stream is coalesced, so gaps
    inside a chunk are zero."""
    out = []
    for r in window_requests(run["mix"], run):
        if r["expected"] < 2:
            continue
        if finished(r):
            a = r["arrivals"]
            out.append((a[-1] - a[0]) / (len(a) - 1) * 1e3)
        else:
            first = r["arrivals"][0] if r["arrivals"] else _ref_time(run, r)
            out.append((run["deadline"] - first) / (r["expected"] - 1) * 1e3)
    return out


def tokens_between(run, a: float, b: float) -> int:
    """Output tokens that reached clients in [a, b), whichever request
    they belong to (the ramp's requests finish inside the window)."""
    return sum(1 for r in run["records"] for t in r["arrivals"]
               if a <= t < b)


def in_flight_at(run, t: float) -> int:
    """Requests sent by `t` whose last token had not reached the client."""
    return sum(1 for r in run["records"]
               if r["sent"] is not None and r["sent"] <= t
               and (not r["done"] or not r["arrivals"]
                    or r["arrivals"][-1] > t))


def stalls(run) -> dict:
    """What tells a run in which the host or the replica stood still from
    a quiet one: the longest time in the window in which no token reached
    any client, and the wake-ups of 0.1 s or more that the clock threads
    of this process and of the replica missed from the ramp to the
    window's end. Both processes' time.monotonic() is the host's one
    clock. Diagnostic only: no metric reads it. (/proc/loadavg reads
    0.00 on the chip machine and /proc/stat gave nothing usable, so CPU
    time taken by others cannot be seen from inside.)"""
    a, b = run["t_win0"], run["t_win1"]
    ts = sorted(t for r in run["records"] for t in r["arrivals"]
                if a <= t < b)
    edges = [a] + ts + [b]
    silence = max(y - x for x, y in zip(edges, edges[1:]))

    def inside(gaps):
        return [[round(t - a, 3), round(d, 3)] for t, d in gaps or []
                if t + d >= a and t < b]
    return {"longest_silence_s": silence,
            "client_clock_gaps": inside(run.get("client_clock_gaps")),
            "replica_clock_gaps": inside(run.get("replica_clock_gaps"))}


def counter_delta(run, key: str):
    c = run.get("counters") or {}
    if "t0" not in c or "t1" not in c:
        return None
    return c["t1"][key] - c["t0"][key]


def program_durations(run, program: str) -> list:
    p = (run.get("trace") or {}).get("programs", {}).get(program)
    return p["durations_s"] if p else []


def mean_live_tokens(run, a: float, b: float) -> float:
    """Time average over [a, b) of the K/V positions live in the slot
    pool: a request holds its prompt from its first token on and one more
    position with every token, until its last token."""
    total = 0.0
    for r in run["records"]:
        arr = r["arrivals"]
        if len(arr) < 2:
            continue
        lo, hi = max(a, arr[0]), min(b, arr[-1])
        if hi <= lo:
            continue
        mid = (lo + hi) / 2.0
        grown = (mid - arr[0]) / (arr[-1] - arr[0]) * len(arr)
        total += (r["prompt_len"] + grown) * (hi - lo)
    return total / (b - a)


def summarize(mix, win) -> dict:
    """One sweep row: what the knee is read from."""
    run = dict(win, mix=mix)
    reqs = window_requests(mix, run)
    t = ttfts_ms(run)
    steps = counter_delta(run, "steps")
    return {
        "sent": len(reqs), "failed": failed_count(run),
        "in_flight_at_end": in_flight_at(run, win["t_win1"]),
        "out_tok_s": tokens_between(run, win["t_win0"], win["t_win1"])
        / (win["t_win1"] - win["t_win0"]),
        "ttft_p50_ms": yardstick.median(t) if t else None,
        "ttft_p95_ms": yardstick.percentile(t, 95) if t else None,
        "tpot_p95_ms": yardstick.percentile(tpots_ms(run), 95)
        if t else None,
        "tok_per_step": counter_delta(run, "tokens_generated") / steps
        if steps else None,
        "queue_depth_at_end": win["counters"]["t1"]["queue_depth"],
    }
