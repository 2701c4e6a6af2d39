"""Flight recorder (ray_tpu/_private/events.py): ring-buffer drop
accounting, span/instant recording, trace-context chaining through the
inference engine, chrome-trace + OTLP read side, shutdown flush, and
the end-to-end Serve streaming trace (proxy -> replica -> engine-slot
-> first-token under ONE trace id)."""

import json
import time

import pytest

import ray_tpu
from ray_tpu._private import events
from ray_tpu.util.tracing import task_events_to_chrome, task_events_to_otlp


@pytest.fixture(autouse=True)
def _clean_recorder():
    """Each test starts with an empty ring and default capacity; drops
    accumulated by other tests don't leak into assertions."""
    events.drain()
    events.configure(capacity=8192)
    events.set_enabled(True)
    yield
    events.drain()
    events.configure(capacity=8192)
    events.set_enabled(True)


def _running_rows(rows):
    """Collapse drained GCS rows to their RUNNING entries by name."""
    out = {}
    for r in rows:
        if r.get("state") == "RUNNING":
            out.setdefault(r["name"], []).append(r)
    return out


# ---------------------------------------------------------------- ring unit
def test_ring_overflow_deterministic_drop_accounting():
    events.configure(capacity=16)
    for i in range(50):
        events.record_instant("probe", category="test", i=i)
    st = events.stats()
    assert st["buffered"] == 16
    assert st["dropped_unreported"] == 34         # exactly 50 - 16
    rows = events.drain()
    by_name = _running_rows(rows)
    # the newest records survive and the drop marker carries the count
    kept = sorted(r["attrs"]["i"] for r in by_name["probe"])
    assert kept == list(range(34, 50))
    assert by_name["events.dropped"][0]["attrs"]["count"] == 34
    # drop accounting resets once reported
    events.record_instant("probe2", category="test")
    rows = events.drain()
    assert "events.dropped" not in _running_rows(rows)


def test_disabled_recorder_records_nothing():
    events.set_enabled(False)
    with events.record_span("off", category="test") as sp:
        sp.set(x=1)
    events.record_instant("off2", category="test")
    events.set_enabled(True)
    assert events.drain() == []


def test_span_pairs_merge_shape():
    """A span flushes as a RUNNING/FINISHED pair sharing one task_id —
    the shape the GCS merge folds into a single timeline row."""
    with events.record_span("window", category="test", n=3):
        time.sleep(0.01)
    rows = events.drain()
    assert len(rows) == 2
    running, finished = rows
    assert running["state"] == "RUNNING" and finished["state"] == "FINISHED"
    assert running["task_id"] == finished["task_id"] == running["span_id"]
    assert running["kind"] == "runtime_event"
    assert finished["ts"] >= running["ts"]
    assert running["attrs"] == {"n": 3}


def test_trace_context_nesting():
    root = events.start_span("root", category="test")
    with events.trace_context(root.trace_id, root.span_id):
        assert events.current_context() == (root.trace_id, root.span_id)
        with events.record_span("child", category="test") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_span_id == root.span_id
    root.end()
    assert events.current_context() is None


# ------------------------------------------------------------- read side
def _span_row(name, trace, span, parent, t0, t1, category="engine",
              event_kind="span", **attrs):
    return {"task_id": span, "kind": "runtime_event", "name": name,
            "category": category, "type": "RUNTIME_EVENT",
            "event_kind": event_kind, "trace_id": trace, "span_id": span,
            "parent_span_id": parent, "node_id": "n0", "worker_id": "w0",
            "attrs": attrs, "state": "FINISHED",
            "state_times": {"RUNNING": t0, "FINISHED": t1}}


def _task_row(name, trace, span, parent, t0, t1):
    return {"task_id": "ab" * 12, "name": name, "type": "ACTOR_TASK",
            "trace_id": trace, "span_id": span, "parent_span_id": parent,
            "node_id": "n0", "worker_id": "w0", "state": "FINISHED",
            "state_times": {"RUNNING": t0, "FINISHED": t1}}


def _sample_trace():
    t = "11" * 16
    return [
        _task_row("handle_stream", t, "aa" * 8, "bb" * 8, 10.0, 11.0),
        _span_row("engine.request", t, "cc" * 8, "aa" * 8, 10.1, 10.9,
                  category="serve"),
        _span_row("engine.slot", t, "dd" * 8, "cc" * 8, 10.2, 10.8,
                  slot=0, queue_wait_ms=3.5),
        _span_row("engine.first_token", t, "ee" * 8, "cc" * 8, 10.3, 10.3,
                  category="serve", event_kind="instant", ttft_ms=200.0),
    ]


def test_chrome_trace_runtime_tracks_roundtrip():
    rows = _sample_trace()
    out = json.loads(json.dumps(task_events_to_chrome(rows)))
    assert len(out) == 4
    # monotonic ts, nonnegative dur on every duration event
    ts = [e["ts"] for e in out]
    assert ts == sorted(ts)
    for e in out:
        if e["ph"] == "X":
            assert e["dur"] >= 1.0
        else:
            assert e["ph"] == "i"
    # runtime rows land on per-subsystem tracks; tasks keep node tracks
    pids = {e["name"]: e["pid"] for e in out}
    assert pids["handle_stream"] == "n0"
    assert pids["engine.slot"] == "runtime:engine"
    assert pids["engine.request"] == "runtime:serve"
    slot = next(e for e in out if e["name"] == "engine.slot")
    assert slot["args"]["queue_wait_ms"] == 3.5
    assert slot["args"]["parent_span_id"] == "cc" * 8


def test_otlp_parents_engine_slot_under_request():
    payload = task_events_to_otlp(_sample_trace())
    spans = {s["name"]: s
             for s in payload["resourceSpans"][0]["scopeSpans"][0]["spans"]}
    assert len(spans) == 4
    assert len({s["traceId"] for s in spans.values()}) == 1
    assert spans["engine.request"]["parentSpanId"] == \
        spans["handle_stream"]["spanId"]
    assert spans["engine.slot"]["parentSpanId"] == \
        spans["engine.request"]["spanId"]
    assert spans["engine.first_token"]["parentSpanId"] == \
        spans["engine.request"]["spanId"]
    attrs = {a["key"]: a["value"] for a in spans["engine.slot"]["attributes"]}
    assert attrs["ray_tpu.attr.queue_wait_ms"] == {"doubleValue": 3.5}
    assert attrs["ray_tpu.category"] == {"stringValue": "engine"}


# --------------------------------------------------------- engine spans
def _tiny_engine(n_slots=2, max_len=32):
    import jax
    import numpy as np

    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import TransformerConfig
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, n_kv_heads=2, d_ff=64,
                            max_seq_len=max_len)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return InferenceEngine(model, params,
                           EngineConfig(n_slots=n_slots, max_len=max_len,
                                        prefill_chunk=8,
                                        prefill_budget=16))


def test_engine_spans_one_trace_with_parent_links():
    eng = _tiny_engine()
    root = events.start_span("request.root", category="test")
    with events.trace_context(root.trace_id, root.span_id):
        h = eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
    while eng.step():
        pass
    assert len(h.tokens()) == 4
    root.end()
    by = _running_rows(events.drain())
    slot = by["engine.slot"][0]
    assert slot["trace_id"] == root.trace_id
    assert slot["parent_span_id"] == root.span_id
    assert slot["attrs"]["prompt_tokens"] == 5
    assert slot["attrs"]["queue_wait_ms"] >= 0
    for pre in by["engine.prefill"]:
        assert pre["parent_span_id"] == slot["span_id"]
    # single-occupancy decode steps adopt the request's trace
    for dec in by["engine.decode"]:
        assert dec["trace_id"] == root.trace_id
        assert dec["parent_span_id"] == slot["span_id"]
        assert dec["attrs"]["slots_active"] == 1
    evict = by["engine.evict"][0]
    assert evict["parent_span_id"] == slot["span_id"]
    # compile ticks surface as instants (decode compiles exactly once)
    fns = [c["attrs"]["fn"] for c in by["engine.compile"]]
    assert "decode" in fns and "prefill" in fns


def test_engine_decode_multi_trace_uses_engine_root():
    eng = _tiny_engine(n_slots=2)
    r1 = events.start_span("req1", category="test")
    r2 = events.start_span("req2", category="test")
    with events.trace_context(r1.trace_id, r1.span_id):
        h1 = eng.submit([1, 2, 3], max_new_tokens=6)
    with events.trace_context(r2.trace_id, r2.span_id):
        h2 = eng.submit([4, 5, 6], max_new_tokens=6)
    while eng.step():
        pass
    h1.tokens(), h2.tokens()
    r1.end(), r2.end()
    by = _running_rows(events.drain())
    both = [d for d in by["engine.decode"]
            if d["attrs"]["slots_active"] == 2]
    assert both, "no decode step saw both requests co-resident"
    for d in both:
        # two distinct traces in one batch -> neutral engine-root trace
        assert d["trace_id"] not in (r1.trace_id, r2.trace_id)


def test_gcs_merge_and_exports_roundtrip():
    """Drained rows fold through the REAL GCS handler (RUNNING/FINISHED
    pairs merge into one row) and both exporters consume the result."""
    from ray_tpu._private.gcs import GcsServer
    g = GcsServer()
    with events.record_span("engine.decode", category="engine", tokens=4):
        pass
    events.record_instant("engine.compile", category="engine", fn="decode")
    g.h_add_task_events(None, events.drain())
    out = g.h_list_task_events(None, limit=100, kind="runtime_event")
    assert len(out) == 2
    span_row = next(r for r in out if r["name"] == "engine.decode")
    assert {"RUNNING", "FINISHED"} <= set(span_row["state_times"])
    assert span_row["attrs"] == {"tokens": 4}
    inst = next(r for r in out if r["name"] == "engine.compile")
    assert inst["event_kind"] == "instant"
    # kind/category filters
    assert g.h_list_task_events(None, kind="task") == []
    assert g.h_list_task_events(None, kind="runtime_event",
                                category="store") == []
    assert len(g.h_list_task_events(None, kind="runtime_event",
                                    category="engine")) == 2
    chrome = task_events_to_chrome(out)
    assert {e["ph"] for e in chrome} == {"X", "i"}
    spans = task_events_to_otlp(
        out)["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert len(spans) == 2


# ------------------------------------------------- prometheus rendering
def test_render_prometheus_escapes_label_values():
    """Backslash / quote / newline in tag values and HELP text emit
    valid exposition format, and the tag value never swaps in for the
    sample value (the shadowed-loop-variable bug class)."""
    from ray_tpu.util.metrics import render_prometheus
    snap = {"w1": [
        {"name": "g", "type": "gauge", "help": "line1\nline2\\x",
         "samples": [[[["zone", 'a"b\\c\nd']], 2.5]]},
        {"name": "c", "type": "counter", "help": "",
         "samples": [[[["t", "v"]], 7.0]]},
        {"name": "h", "type": "histogram", "help": "hh",
         "boundaries": [1.0],
         "samples": [[[["q", 'x"y']], [2, 1], 0.5]]},
    ]}
    text = render_prometheus(snap)
    assert "# HELP g line1\\nline2\\\\x" in text
    assert 'g{zone="a\\"b\\\\c\\nd"} 2.5' in text
    # no raw newline may survive inside a sample line
    for line in text.splitlines():
        assert not line.endswith("\\")
    # sample value stays the metric value, not the tag value
    assert 'c{t="v"} 7.0' in text
    assert 'h_bucket{q="x\\"y",le="1.0"} 2' in text
    assert 'h_bucket{q="x\\"y",le="+Inf"} 3' in text
    assert 'h_count{q="x\\"y"} 3' in text


def test_render_prometheus_aggregates_across_workers():
    from ray_tpu.util.metrics import render_prometheus
    row = {"name": "c", "type": "counter", "help": "",
           "samples": [[[["k", "a"]], 2.0]]}
    text = render_prometheus({"w1": [row], "w2": [row]})
    assert 'c{k="a"} 4.0' in text


# ------------------------------------------------------ cluster-side
@pytest.fixture(scope="module")
def cluster():
    from ray_tpu.cluster_utils import Cluster
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    yield c
    c.shutdown()


def test_spans_survive_worker_shutdown_flush(cluster):
    """Spans recorded by a driver that exits before the 1s flusher
    cadence reach the GCS through the stop_async flush."""
    ray_tpu.init(address=cluster.address)
    try:
        marker = f"shutdown-span-{time.monotonic_ns()}"
        with events.record_span(marker, category="test"):
            pass
    finally:
        ray_tpu.shutdown()     # flush happens here, NOT via the flusher
    ray_tpu.init(address=cluster.address)
    try:
        rows = ray_tpu._get_worker().gcs_call(
            "list_task_events", limit=20000, kind="runtime_event")
        names = {r.get("name") for r in rows}
        assert marker in names, sorted(names)[:20]
    finally:
        ray_tpu.shutdown()


def test_serve_streaming_end_to_end_trace(cluster, tmp_path):
    """Acceptance: one streaming Serve request through the HTTP proxy
    produces a single trace — proxy, replica task, engine
    prefill/decode/slot, first-token — with correct parent links,
    visible in both the chrome-trace and OTLP exports."""
    import urllib.request

    from ray_tpu import serve
    from ray_tpu.inference import LLMDeployment
    from ray_tpu.models.transformer import TransformerConfig
    ray_tpu.init(address=cluster.address)
    try:
        cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=2, n_kv_heads=2, d_ff=64,
                                max_seq_len=64)
        app = serve.deployment(LLMDeployment).bind(
            cfg, n_slots=2, max_len=32, prefill_chunk=8,
            prefill_budget=16)
        serve.run(app, name="llm", _http=True, http_port=8130)
        addr = next(iter(serve.proxies().values()))["http"]
        body = json.dumps([1, 2, 3, 4]).encode()
        req = urllib.request.Request(
            f"http://{addr}/", data=body,
            headers={"Content-Type": "application/json",
                     "X-RayTPU-Stream": "1"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            chunks = [json.loads(ln) for ln in
                      resp.read().decode().splitlines()]
        assert len(chunks) >= 4 and all(
            isinstance(c, int) for c in chunks), chunks[:8]

        want = ["proxy.request", "handle_stream", "engine.request",
                "engine.slot", "engine.prefill", "engine.decode",
                "engine.first_token"]
        deadline = time.monotonic() + 60
        rows = []
        while time.monotonic() < deadline:
            rows = ray_tpu._get_worker().gcs_call("list_task_events",
                                                  limit=20000)
            have = {r.get("name") for r in rows}
            if all(n in have for n in want):
                break
            time.sleep(0.5)
        by = {}
        for r in rows:
            by.setdefault(r.get("name"), []).append(r)
        missing = [n for n in want if n not in by]
        assert not missing, f"missing {missing}"

        proxy = by["proxy.request"][0]
        trace = proxy["trace_id"]
        replica_task = next(r for r in by["handle_stream"]
                            if r.get("trace_id") == trace)
        request = next(r for r in by["engine.request"]
                       if r.get("trace_id") == trace)
        slot = next(r for r in by["engine.slot"]
                    if r.get("trace_id") == trace)
        first = next(r for r in by["engine.first_token"]
                     if r.get("trace_id") == trace)
        decodes = [r for r in by["engine.decode"]
                   if r.get("trace_id") == trace]
        prefills = [r for r in by["engine.prefill"]
                    if r.get("trace_id") == trace]
        assert replica_task["parent_span_id"] == proxy["span_id"]
        assert request["parent_span_id"] == replica_task["span_id"]
        assert slot["parent_span_id"] == request["span_id"]
        assert first["parent_span_id"] == request["span_id"]
        assert prefills and all(p["parent_span_id"] == slot["span_id"]
                                for p in prefills)
        assert decodes, "no decode spans joined the request trace"

        # same trace visible in both export formats
        chrome = ray_tpu.timeline()
        in_trace = [e for e in chrome
                    if e["args"].get("trace_id") == trace]
        chrome_names = {e["name"] for e in in_trace}
        for n in ("proxy.request", "engine.slot", "engine.first_token"):
            assert n in chrome_names
        otlp = task_events_to_otlp(rows)
        ospans = [s for s in
                  otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]
                  if s["traceId"] == trace]
        onames = {s["name"] for s in ospans}
        for n in want:
            assert n in onames, (n, sorted(onames))
        oslot = next(s for s in ospans if s["name"] == "engine.slot")
        orequest = next(s for s in ospans
                        if s["name"] == "engine.request")
        assert oslot["parentSpanId"] == orequest["spanId"]
    finally:
        from ray_tpu import serve as _serve
        try:
            _serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
