"""A replica's start, told by phase (PR 56): the compile watch of
`_private/compile_cache.py` (`xla.trace` / `xla.lower` / `xla.compile` on
the flight recorder, totals beside the ring), `events.launch_phase`, the
phases `LLMDeployment` and its engine record under `launch.callable_init`,
what `InferenceEngine.stats()` says of them, and the one trace a served
replica's launch leaves from `actor.launch` down to a compile."""

import contextlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu._private import compile_cache, events

RAY_START = dict(num_cpus=4)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATS_KEYS = ("startup_t_mono", "startup_init_s", "startup_weights_s",
              "startup_engine_s", "startup_pools_s", "startup_programs_s",
              "xla_compiles", "xla_cache_hits", "xla_cache_misses",
              "xla_trace_s", "xla_lower_s", "xla_compile_s",
              "xla_cache_load_s")


@pytest.fixture(autouse=True)
def _clean_recorder():
    events.drain()
    events.set_enabled(True)
    yield
    events.drain()
    events.set_enabled(True)


def _spans(prefix):
    return [r for r in events.peek() if r["name"].startswith(prefix)]


@contextlib.contextmanager
def _root():
    """An open span that is the thread's trace context."""
    with events.record_span("root", category="test") as root, \
            events.trace_context(root.trace_id, root.span_id):
        yield root


def _fresh_program():
    """A jitted function no test has called: inner `jit`s (matmul, tanh,
    a reduction) under an outer one."""
    def program(x):
        return jnp.tanh(x @ x.T).sum()
    program.__name__ = f"program_{time.monotonic_ns()}"
    return jax.jit(program), program.__name__


# ------------------------------------------------------------ the watch
def test_first_call_leaves_three_spans_under_the_open_context():
    assert compile_cache.watch() is True
    assert compile_cache.watch() is True            # idempotent
    fn, name = _fresh_program()
    x = jnp.ones((64, 64))
    events.drain()
    before = compile_cache.totals()
    with _root() as outer:
        fn(x).block_until_ready()
    mine = [r for r in _spans("xla.") if r["attrs"]["fun"] == name]
    assert [r["name"] for r in mine] == ["xla.trace", "xla.lower",
                                        "xla.compile"]
    for r in mine:
        assert r["category"] == "compile"
        assert r["trace_id"] == outer.trace_id
        assert r["parent_span_id"] == outer.span_id
        assert outer.start <= r["start"] <= r["end"]
    trace, lower, comp = mine
    assert trace["end"] <= lower["start"] + 1e-6
    assert lower["end"] <= comp["start"] + 1e-6
    assert comp["attrs"]["cache"] in ("hit", "miss", "off")
    after = compile_cache.totals()
    assert after["compiles"] == before["compiles"] + 1
    for key, rec in (("trace_s", trace), ("lower_s", lower),
                     ("compile_s", comp)):
        assert after[key] - before[key] == pytest.approx(
            rec["end"] - rec["start"], abs=1e-6)


def test_one_trace_span_a_program_not_one_a_traced_function():
    """JAX reports matmul, tanh, the reduction and the outer function,
    the inner ones first: the ring gets the outer one alone and
    `trace_s` its seconds once."""
    compile_cache.watch()
    seen = []
    listener = lambda event, start, end, **kw: seen.append(   # noqa: E731
        (event, kw.get("fun_name")))
    jax.monitoring.register_event_time_span_listener(listener)
    try:
        fn, name = _fresh_program()
        x = jnp.ones((48, 48))
        seen.clear()
        events.drain()
        before = compile_cache.totals()["trace_s"]
        fn(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_time_span_listener(listener)
    reported = [f for e, f in seen if e.endswith("jaxpr_trace_duration")]
    assert len(reported) > 1 and reported[-1] == name, reported
    traces = _spans("xla.trace")
    assert [r["attrs"]["fun"] for r in traces] == [name]
    assert compile_cache.totals()["trace_s"] - before == pytest.approx(
        traces[0]["end"] - traces[0]["start"], abs=1e-6)


def test_second_call_leaves_nothing_and_raises_nothing():
    compile_cache.watch()
    fn, _ = _fresh_program()
    x = jnp.ones((32, 32))
    fn(x).block_until_ready()
    events.drain()
    before = compile_cache.totals()
    fn(x).block_until_ready()
    assert _spans("xla.") == []
    assert compile_cache.totals() == before


def _run(code, **env):
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240, env={**os.environ, "PYTHONPATH": REPO, **env})


def test_a_process_without_jax_gets_no_listener_and_imports_none():
    out = _run(
        "import sys\n"
        "from ray_tpu._private import compile_cache, events\n"
        "compile_cache.configure_compile_cache()\n"
        "assert compile_cache.watch() is False\n"
        "with events.launch_phase('weights'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print(compile_cache.totals()['compiles'])\n")
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "0"


_CACHED_RUN = """
import json, jax, jax.numpy as jnp
from ray_tpu._private import compile_cache, events
compile_cache.configure_compile_cache()     # jax is imported: it watches
def program(x):
    return jnp.tanh(x @ x.T).sum()
jax.jit(program)(jnp.ones((64, 64))).block_until_ready()
rec = [r for r in events.peek() if r["name"] == "xla.compile"
       and r["attrs"]["fun"] == "program"]
print(json.dumps({"attrs": rec[0]["attrs"], **compile_cache.totals()}))
"""


def test_the_persistent_cache_is_told_miss_then_hit(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
           "JAX_PLATFORMS": "cpu"}
    runs = []
    for _ in range(2):
        out = _run(_CACHED_RUN, **env)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["attrs"]["cache"] == "miss" and cold["attrs"]["stored"]
    assert cold["cache_hits"] == 0 and cold["cache_misses"] >= 1
    assert warm["attrs"]["cache"] == "hit"
    assert warm["attrs"]["load_s"] > 0
    assert warm["cache_hits"] >= 1 and warm["cache_hits"] <= warm["compiles"]
    assert warm["cache_load_s"] >= warm["attrs"]["load_s"] - 1e-6
    assert warm["compile_s"] >= warm["cache_load_s"] - 1e-6


# ------------------------------------------------------- launch phases
def test_launch_phase_is_a_span_a_context_and_a_kept_duration():
    t_before = time.monotonic()
    with _root() as root:
        with events.launch_phase("callable_init", actor_id="a") as phase:
            assert events.current_context() == (phase.trace_id,
                                                phase.span_id)
            events.record_instant("inside", category="test")
        assert events.current_context() == (root.trace_id, root.span_id)
    by = {r["name"]: r for r in events.peek()}
    rec = by["launch.callable_init"]
    assert rec["category"] == "launch"
    assert rec["parent_span_id"] == root.span_id
    assert by["inside"]["parent_span_id"] == rec["span_id"]
    assert rec["attrs"]["actor_id"] == "a"
    assert t_before <= rec["attrs"]["t_mono"] <= time.monotonic()
    t_mono, seconds = events.launch_phases()["callable_init"]
    assert t_mono == rec["attrs"]["t_mono"]
    assert seconds == pytest.approx(rec["end"] - rec["start"], abs=0.05)
    from ray_tpu.util.metrics import registry_snapshot
    gauge = next(m for m in registry_snapshot()
                 if m["name"] == "runtime_launch_phase_ms")
    phases = {dict(map(tuple, k))["phase"]: v for k, v in gauge["samples"]}
    assert phases["callable_init"] == pytest.approx(seconds * 1e3, abs=0.01)


def test_launch_phase_names_its_error_and_keeps_the_newest():
    with pytest.raises(ZeroDivisionError):
        with events.launch_phase("weights", source="loader"):
            1 / 0
    rec = _spans("launch.weights")[-1]
    assert rec["attrs"]["error"] == "ZeroDivisionError"
    first = events.launch_phases()["weights"]
    with events.launch_phase("weights", source="arena"):
        pass
    assert events.launch_phases()["weights"] != first


def test_launch_phase_with_the_recorder_off_keeps_time_and_context():
    events.set_enabled(False)
    with events.trace_context("t" * 32, "s" * 16):
        with events.launch_phase("engine"):
            assert events.current_context() == ("t" * 32, "s" * 16)
    events.set_enabled(True)
    assert _spans("launch.") == []
    assert events.launch_phases()["engine"][1] >= 0.0


def test_weight_source_is_a_phase_that_says_where_the_tree_came_from():
    from ray_tpu.serve.weights import resolve_weight_source
    assert resolve_weight_source(None, lambda: {"w": 1}) == {"w": 1}
    rec = _spans("launch.weights")[-1]
    assert rec["attrs"]["source"] == "loader"
    assert rec["attrs"]["published"] is False and rec["attrs"]["key"] is None
    assert not _spans("serve.weight_attach")


# ---------------------------------------------- LLMDeployment, bare
@pytest.fixture(scope="module")
def deployment():
    from ray_tpu.inference import LLMDeployment
    events.drain()
    with _root() as root:
        with events.launch_phase("callable_init", actor_id="bare"):
            d = LLMDeployment("llama-debug")
    records = events.peek()
    yield d, root, records
    d.engine.stop()


def test_deployment_leaves_its_phases_under_the_context_it_was_built_in(
        deployment):
    _, root, records = deployment
    by = {}
    for r in records:
        by.setdefault(r["name"], []).append(r)
    (init,), (weights,), (engine,) = (by["launch.callable_init"],
                                      by["launch.weights"],
                                      by["launch.engine"])
    (pools,), (programs,) = (by["launch.engine.pools"],
                             by["launch.engine.programs"])
    assert init["parent_span_id"] == root.span_id
    assert weights["attrs"]["source"] == "init"
    for child, parent in ((weights, init), (engine, init),
                          (pools, engine), (programs, engine)):
        assert child["trace_id"] == root.trace_id
        assert child["parent_span_id"] == parent["span_id"]
        assert parent["start"] <= child["start"]
        assert child["end"] <= parent["end"]
    assert weights["end"] <= engine["start"]
    assert pools["end"] <= programs["start"]
    # the compiles fall under whichever phase was open: the tile
    # program's under `launch.engine.programs`
    prefill = [r for r in records if r["name"].startswith("xla.")
               and r["attrs"]["fun"] == "prefill"]
    assert {r["name"] for r in prefill} == {"xla.trace", "xla.lower",
                                            "xla.compile"}
    assert all(r["parent_span_id"] == programs["span_id"] for r in prefill)
    under_weights = [r for r in records if r["name"] == "xla.compile"
                     and r["parent_span_id"] == weights["span_id"]]
    assert under_weights, "model.init compiled nothing under launch.weights"


def test_stats_hold_the_thirteen_keys(deployment):
    d, _, records = deployment
    st = d.engine.stats()
    for key in STATS_KEYS:
        assert key in st and st[key] >= 0, (key, st.get(key))
    assert st["startup_weights_s"] + st["startup_engine_s"] \
        <= st["startup_init_s"]
    assert st["startup_pools_s"] + st["startup_programs_s"] \
        <= st["startup_engine_s"]
    init = next(r for r in records if r["name"] == "launch.callable_init")
    assert st["startup_t_mono"] == init["attrs"]["t_mono"]
    assert st["xla_compiles"] > 0 and st["xla_compile_s"] > 0
    assert st["xla_cache_hits"] <= st["xla_compiles"]
    # a request compiles the decode program: the totals move on
    d.generate([1, 2, 3], max_new_tokens=3)
    assert d.engine.stats()["xla_compiles"] > st["xla_compiles"]


# ------------------------------------- a served replica's launch trace
def test_a_served_replica_launch_is_one_trace_down_to_a_compile(ray_start):
    """`actor.launch` (GCS) -> `launch.callable_init` (worker) ->
    `launch.weights` / `launch.engine` -> its two parts -> `xla.*`, one
    trace id, on the timeline."""
    from ray_tpu import serve
    from ray_tpu.inference import LLMDeployment
    from ray_tpu.models.transformer import TransformerConfig
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, n_kv_heads=2, d_ff=64,
                            max_seq_len=64)
    app = serve.deployment(LLMDeployment).bind(
        cfg, n_slots=2, max_len=32, prefill_chunk=8, prefill_budget=16)
    try:
        serve.run(app, name="llm")
        h = serve.get_app_handle("llm")
        stats = h.stats.remote().result(timeout=120)
        for key in STATS_KEYS:
            assert key in stats, key
        want = {"launch.callable_init", "launch.weights", "launch.engine",
                "launch.engine.pools", "launch.engine.programs"}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            rows = ray_tpu._get_worker().gcs_call(
                "list_task_events", limit=20000, kind="runtime_event")
            weights = [r for r in rows if r.get("name") == "launch.weights"]
            if weights and want <= {r.get("name") for r in rows
                                    if r.get("trace_id")
                                    == weights[0]["trace_id"]}:
                break
            time.sleep(0.5)
        trace = weights[0]["trace_id"]
        by = {}
        for r in rows:
            if r.get("trace_id") == trace:
                by.setdefault(r["name"], []).append(r)
        assert want <= set(by), sorted(by)
        root = by["actor.launch"][0]
        init = by["launch.callable_init"][0]
        assert init["parent_span_id"] == root["span_id"]
        assert init["attrs"]["t_mono"] == stats["startup_t_mono"]
        engine = by["launch.engine"][0]
        programs = by["launch.engine.programs"][0]
        assert by["launch.weights"][0]["parent_span_id"] == init["span_id"]
        assert engine["parent_span_id"] == init["span_id"]
        assert programs["parent_span_id"] == engine["span_id"]
        compiles = [r for r in by["xla.compile"]
                    if r["parent_span_id"] == programs["span_id"]]
        assert any(r["attrs"]["fun"] == "prefill" for r in compiles)
        assert all(r["attrs"]["cache"] in ("hit", "miss", "off")
                   for r in by["xla.compile"])
        names = {e["name"] for e in ray_tpu.timeline()
                 if e["args"].get("trace_id") == trace}
        assert want | {"actor.launch", "xla.trace", "xla.lower",
                       "xla.compile"} <= names
    finally:
        serve.shutdown()
