"""A serving cell: `serve.run` of one replica on the chip, load from this
process (which never opens JAX), grown from chip_smoke.py's serve_phase.

Set-up (replica start with seeded weights, a warm-up request that crosses
prefill, decode and a prefix-block save and load, the ramp) ends where the
window begins. In the window requests are sent when they are due (open
loop) or when a client's last one ended (closed loop), one thread per
request or per client, and every token's arrival is stamped with this
process's monotonic clock. After the window: a drain of at most
DRAIN_S, the probe again, the teacher-forced reference check, the
replica's own clocks and counters, shutdown.
"""

from __future__ import annotations

import os
import shutil
import sys
import threading
import time

from perfbench import spec, traffic
from perfbench.checks import Checks

DRAIN_S = 20.0          # a request still unfinished this long after the
                        # window counts as failed, and enters the tails here
PROBE_PROMPT = 300      # three prefill chunks of 128, two full ones saved
PROBE_NEW = 16
REFERENCE_CASES = ((200, 24), (700, 24))    # (prompt, generated) tokens,
                        # where the mix states no `reference_cases`


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _Record:
    __slots__ = ("idx", "due", "sent", "arrivals", "expected", "error",
                 "done", "prompt_len", "client", "tokens")

    def __init__(self, req, due):
        self.idx, self.due, self.client = req.idx, due, req.client
        self.sent = None
        self.arrivals = []           # monotonic time of each token
        self.expected = req.max_new_tokens
        self.prompt_len = len(req.prompt)
        self.error = None
        self.done = False
        self.tokens = None

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


def _consume(stream, req, rec, vocab, keep_tokens=False):
    """Send one request and stamp each token as it reaches this client."""
    toks = []
    rec.sent = time.monotonic()
    try:
        for tok in stream.remote(req.prompt,
                                 max_new_tokens=req.max_new_tokens,
                                 bench_id=req.idx):
            rec.arrivals.append(time.monotonic())
            toks.append(tok)
    except Exception as e:                       # counted, not raised
        rec.error = f"{type(e).__name__}: {e}"
    if rec.error is None and not (
            len(toks) == req.max_new_tokens
            and all(isinstance(t, int) and 0 <= t < vocab for t in toks)):
        rec.error = (f"returned {len(toks)} tokens, wanted "
                     f"{req.max_new_tokens} ids below {vocab}")
    if keep_tokens:
        rec.tokens = toks
    rec.done = True


def _open_loop(stream, reqs, t_win0, vocab, stop):
    records, threads = [], []

    def dispatch():
        for req in reqs:
            due = t_win0 + req.due_s
            delay = due - time.monotonic()
            if delay > 0 and stop.wait(delay):
                return
            if stop.is_set():
                return
            rec = _Record(req, due)
            th = threading.Thread(target=_consume, daemon=True,
                                  args=(stream, req, rec, vocab))
            records.append(rec)
            threads.append(th)
            th.start()

    d = threading.Thread(target=dispatch, daemon=True)
    d.start()
    return d, records, threads


def _closed_loop(stream, reqs, t_win0, ramp_s, vocab, stop, clients):
    records, lock = [], threading.Lock()

    def client(c):
        # clients start spread over the first half of the ramp, so that
        # their prefills do not march in step
        start = t_win0 - ramp_s + (ramp_s / 2.0) * c / max(1, clients)
        if stop.wait(max(0.0, start - time.monotonic())):
            return
        for req in reqs[c::clients]:          # traffic.py deals them so
            if stop.is_set():
                return
            rec = _Record(req, None)
            with lock:
                records.append(rec)
            _consume(stream, req, rec, vocab)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for th in threads:
        th.start()
    return None, records, threads


def run_window(h, mix, reqs, seconds, vocab, trace_dir=None,
               drain_s=None):
    """Ramp, window, drain. Returns the window's bounds, the requests'
    records and the engine's counters at the counter window's ends."""
    stream = h.options(stream=True)
    ramp_s = float(mix.get("ramp_s", 0.0))
    trace_s = float(mix.get("trace_s", 4.0)) if trace_dir else 0.0
    stop = threading.Event()
    clock_gaps, clock_stop = [], threading.Event()
    from perfbench.runtime import watch_clock
    threading.Thread(target=watch_clock, args=(clock_stop, clock_gaps),
                     daemon=True).start()
    t_win0 = time.monotonic() + ramp_s + 0.25
    t_win1 = t_win0 + seconds
    if mix["driver"] == "open":
        disp, records, threads = _open_loop(stream, reqs, t_win0, vocab,
                                            stop)
    else:
        disp, records, threads = _closed_loop(
            stream, reqs, t_win0, ramp_s, vocab, stop, int(mix["clients"]))
    counters = {}

    def at(t, key, call):
        time.sleep(max(0.0, t - time.monotonic()))
        counters[key] = call().result(timeout=120)

    # with a trace, the counters' window ends where the traced slice
    # begins: the profiler slows the host it runs on
    t_c1 = t_win1 - trace_s
    at(t_win0, "t0", h.bench_counters.remote)
    at(t_c1, "t1", h.bench_counters.remote)
    traced = None
    if trace_dir:
        t_a = h.bench_trace_start.remote(trace_dir).result(timeout=120)
        time.sleep(max(0.0, t_win1 - time.monotonic()))
        t_b = h.bench_trace_stop.remote().result(timeout=300)
        traced = (t_a, t_b)
    time.sleep(max(0.0, t_win1 - time.monotonic()))
    stop.set()
    clock_stop.set()
    deadline = t_win1 + (DRAIN_S if drain_s is None else drain_s)
    if disp is not None:
        disp.join(timeout=5)
    for th in list(threads):
        th.join(timeout=max(0.0, deadline - time.monotonic()))
    return {"t_win0": t_win0, "t_win1": t_win1, "deadline": deadline,
            "counters": counters, "traced": traced,
            "client_clock_gaps": list(clock_gaps),
            "records": [r.as_dict() for r in list(records)]}


def _probe(stream, prompt, n_new, vocab, idx):
    req = traffic.Request(idx, None, None, prompt, n_new)
    rec = _Record(req, None)
    _consume(stream, req, rec, vocab, keep_tokens=True)
    return rec


def run(args, cell, cfg, mix, t_start, checks: Checks) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from perfbench.replica import BenchLLMDeployment
    from perfbench.runtime import shutdown_and_verify

    vocab = cfg["vocab_size"]
    engine = dict(cfg["engine"])
    max_ongoing = engine.pop("max_ongoing_requests", 64)
    model_kwargs = spec.family_of(cfg).model_kwargs(cfg)
    reqs = traffic.schedule(mix, args.seed, args.seconds, vocab,
                            engine["max_len"])
    rng = np.random.default_rng([int(args.seed), 99])
    probe_prompt = rng.integers(1, vocab, size=min(
        PROBE_PROMPT, engine["max_len"] // 2)).tolist()
    ref_cases = [(rng.integers(1, vocab, size=min(
        p, engine["max_len"] // 2)).tolist(), g)
        for p, g in mix.get("reference_cases", REFERENCE_CASES)]
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(args.out_dir, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    ray_tpu.init(resources={"TPU": cell["chips"]} if args.rehearse else None)
    out = {"kind": "serve"}
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        if advertised < cell["chips"]:
            raise SystemExit(f"the node advertises TPU={advertised}; the "
                             f"cell needs {cell['chips']}")
        app = serve.deployment(
            BenchLLMDeployment, max_ongoing_requests=max_ongoing,
            ray_actor_options={"num_tpus": cell["chips"]}).bind(
            model_kwargs, {k: v for k, v in cfg.items() if k != "_entry"},
            args.seed, **engine)
        serve.run(app, name="llm")
        h = serve.get_app_handle("llm")
        device = h.bench_device.remote().result(timeout=1100)
        log(f"device: {device}")
        if device["platform"] != "tpu" and not args.rehearse:
            raise SystemExit(f"the replica runs on {device['platform']!r}, "
                             f"not on a TPU: no measurement")
        if device["count"] != cell["chips"] and not args.rehearse:
            raise SystemExit(f"the replica sees {device['count']} chips, "
                             f"the cell needs {cell['chips']}")
        stream = h.options(stream=True)
        # warm-up: prefill (several chunks), insert, decode, the prefix
        # blocks' save; the same prompt again loads them
        t0 = time.monotonic()
        probe0 = _probe(stream, probe_prompt, PROBE_NEW, vocab, "warm0")
        probe1 = _probe(stream, probe_prompt, PROBE_NEW, vocab, "warm1")
        log(f"warm-up: {time.monotonic() - t0:.1f}s "
            f"errors={probe0.error, probe1.error}")
        checks.check(probe0.error is None and probe1.error is None,
                     f"warm-up requests failed: {probe0.error}, "
                     f"{probe1.error}")
        warm = h.bench_counters.remote().result(timeout=60)

        if args.sweep_rates:
            return _sweep(args, h, mix, vocab, engine, device)

        win = run_window(h, mix, reqs, args.seconds, vocab, trace_dir)
        out.update(win)
        out["setup_s"] = win["t_win0"] - t_start
        probe2 = _probe(stream, probe_prompt, PROBE_NEW, vocab, "after")
        checks.check(probe2.error is None
                     and probe2.tokens == probe0.tokens == probe1.tokens,
                     f"the probe request gave other tokens after the window "
                     f"than before: {probe0.tokens} / {probe1.tokens} / "
                     f"{probe2.tokens} ({probe2.error})")
        # the teacher-forced reference check, on the replica's weights
        cases = []
        for i, (prompt, n_new) in enumerate(ref_cases):
            rec = _probe(stream, prompt, n_new, vocab, f"ref{i}")
            checks.check(rec.error is None, f"reference case {i}: "
                                            f"{rec.error}")
            cases.append((prompt, rec.tokens or [0]))
        t0 = time.monotonic()
        ref = h.bench_reference.remote(cases).result(timeout=900)
        gaps = ref["gaps"]
        tol = cfg["reference_tolerance"]
        flat = [x for g in gaps for x in g]
        out["reference"] = {
            "seconds": time.monotonic() - t0, "n_tokens": len(flat),
            "logit_gap": tol["logit_gap"],
            "share_within_gap": sum(x <= tol["logit_gap"] for x in flat)
            / len(flat),
            "max_gap": max(flat), "logit_std": ref["logit_std"],
            "argmax_share": sum(x == 0.0 for x in flat) / len(flat)}
        log(f"reference: {out['reference']}")
        checks.check(
            out["reference"]["share_within_gap"] >= tol["share_within"],
            f"only {out['reference']['share_within_gap']:.3f} of the "
            f"generated tokens have a reference logit within "
            f"{tol['logit_gap']} of their position's largest (the largest "
            f"gap is {out['reference']['max_gap']:.3f}); "
            f"{tol['share_within']} must")
        end = h.bench_counters.remote().result(timeout=60)
        for key in ("decode_compile_count", "prefill_compile_count"):
            checks.check(end[key] == warm[key],
                         f"{key} went from {warm[key]} after the warm-up to "
                         f"{end[key]}: something compiled after it")
        out["replica_timings"] = h.bench_timings.remote().result(timeout=60)
        out["replica_clock_gaps"] = h.bench_clock_gaps.remote().result(
            timeout=60)
        out["device"] = h.bench_device.remote().result(timeout=60)
    finally:
        shutdown_and_verify(checks, serve=True)
    return out


def _sweep(args, h, mix, vocab, engine, device) -> dict:
    """Find the knee once: one replica, one window per rate. Prints a row
    per rate; no result line."""
    import json
    rows = []
    for rate in args.sweep_rates:
        m = dict(mix, arrival=dict(mix["arrival"], rate_per_s=rate))
        reqs = traffic.schedule(m, args.seed, args.seconds, vocab,
                                engine["max_len"])
        # a longer drain: the next rate's window must open on an empty engine
        win = run_window(h, m, reqs, args.seconds, vocab, drain_s=60.0)
        from perfbench.metrics_lib import summarize
        row = {"platform": device["platform"], "rate_per_s": rate,
               **summarize(m, win)}
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    return {"kind": "sweep", "rows": rows, "device": device}
