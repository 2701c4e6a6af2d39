"""The engine's step told by phase (inference/engine.py `_StepPhases`,
_private/events.py `annotate`): the spans on the profiler's own clock, the
counters of a request's wait in `stats()`, what `on_step` is handed, and
the step programs' text, which this instrumentation must not touch.
CPU-only, no cluster."""
import hashlib
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import host_spans, trace_reduce
from ray_tpu._private import events
from ray_tpu.inference.engine import PHASES, EngineConfig, InferenceEngine
from ray_tpu.models import TransformerLM
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.util import metrics as metrics_mod
from tests.test_fused_step import tile_text as _tile_text

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_recorder():
    events.drain()
    yield
    events.drain()


def _engine(n_slots=2, max_len=64, d_model=32, n_layers=2, **model):
    cfg = TransformerConfig(**dict(
        vocab_size=64, d_model=d_model, n_layers=n_layers, n_heads=2,
        n_kv_heads=2, d_ff=2 * d_model, max_seq_len=max_len) | model)
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0),
                     np.zeros((1, 8), np.int32))["params"]
    return InferenceEngine(lm, params, EngineConfig(
        n_slots=n_slots, max_len=max_len, prefill_chunk=8,
        prefill_budget=16))


# ------------------------------------------------ the profiler's own trace
def _traced_steps(eng, tmp_path, drive):
    """Step `eng` under the profiler as the harness starts it -> (steps,
    phases) of the engine's thread, as perfbench/host_spans.py reads them."""
    trace_reduce.start(str(tmp_path))
    try:
        drive(eng)
    finally:
        trace_reduce.stop()
    planes = list(host_spans.read_xplane(
        trace_reduce.find_xplane(str(tmp_path))))
    (line,) = host_spans._engine_lines(planes)
    return host_spans._whole_steps(line)


def _one_request(eng):
    h = eng.submit(list(range(1, 30)), max_new_tokens=5)
    while eng.step():
        pass
    assert len(h.tokens()) == 5


def _riding_rows(eng):
    # the second prompt's tiles carry the first request's decode row
    first = eng.submit(list(range(1, 12)), max_new_tokens=12)
    eng.step()
    eng.step()
    second = eng.submit(list(range(1, 40)), max_new_tokens=3)
    while eng.step():
        pass
    assert len(first.tokens()) == 12 and len(second.tokens()) == 3


@pytest.mark.parametrize("model,drive,rides", [
    ({}, _one_request, True),
    ({}, _riding_rows, True),
    (dict(index_heads=2, index_head_dim=16, index_topk=16), _riding_rows,
     False)], ids=["dense", "tile_with_riding_rows", "indexer_rows_do_not_ride"])
def test_a_traced_step_is_tiled_by_its_phases(tmp_path, model, drive, rides):
    # wide enough that a step is tens of milliseconds on the CPU: a seam
    # between two annotations is about 5 us with the profiler on
    eng = _engine(d_model=512, n_layers=6, **model)
    assert eng._ride is rides
    _one_request(eng)                         # every program has run once
    steps0, fused0 = eng.steps, eng.fused_steps
    t0, w0 = time.monotonic(), time.time()
    steps, phases = _traced_steps(eng, tmp_path, drive)
    assert len(steps) == eng.steps - steps0
    assert [st["step"] for _, _, st in steps] == list(
        range(steps0, eng.steps))
    assert (eng.fused_steps > fused0) is (rides and drive is _riding_rows)
    seen, covers = set(), []
    for (a, b, st), nxt in zip(steps, steps[1:] + [None]):
        # the anchor: both clocks, read where the step began
        assert t0 <= st["t_mono"] <= time.monotonic()
        assert w0 <= st["t_wall"] <= time.time()
        kids = [(s, e, k) for s, e, k in phases if a <= s and e <= b]
        assert kids and kids[0][2] == "plan"
        assert all(k in PHASES for _, _, k in kids)
        assert all(e0 <= s1 for (_, e0, _), (s1, _, _)
                   in zip(kids, kids[1:])), "phases overlap"
        if any(k == "dispatch" for _, _, k in kids):
            covers.append((sum(e - s for s, e, _ in kids), b - a))
            # a seam is microseconds, but on a loaded machine the thread
            # can lose its core in one: every step for the most part,
            # the steps together and the median step to 99%
            assert covers[-1][0] >= 0.9 * covers[-1][1], (st["step"], kids)
        else:       # nothing to run: microseconds, all of it plan
            assert (b - a) - sum(e - s for s, e, _ in kids) < 50e3
        assert nxt is None or b <= nxt[0]
        seen.update(k for _, _, k in kids)
    assert seen == set(PHASES)
    assert sum(c for c, _ in covers) >= 0.99 * sum(n for _, n in covers)
    assert sorted(c / n for c, n in covers)[len(covers) // 2] >= 0.99
    # a step's anchors map one clock onto the other by one number
    offs = [a - st["t_mono"] * 1e9 for a, _, st in steps]
    assert max(offs) - min(offs) < 1e6
    # and the phases of a step cut by the trace's edge are left out
    assert all(any(a <= s and e <= b for a, b, _ in steps)
               for s, e, _ in phases)


def test_annotate_is_nothing_where_jax_is_not_imported():
    code = ("import sys\n"
            "from ray_tpu._private import events\n"
            "with events.annotate('engine.step', step=1, t_mono=2.0):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'events imported jax'\n"
            "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120,
                         env=dict(os.environ, PYTHONPATH=ROOT))
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_annotate_writes_nothing_to_the_ring():
    with events.annotate("engine.plan"):
        pass
    assert events.drain() == []


# ------------------------------------------------------- a request's wait
def test_a_wait_behind_full_slots_is_counted_where_it_happens():
    eng = _engine(n_slots=1)
    before = eng.stats()
    assert [before[k] for k in ("admitted", "queue_wait_s", "first_tokens",
                                "prefill_span_s")] == [0, 0.0, 0, 0.0]
    first = eng.submit(list(range(1, 20)), max_new_tokens=8)
    held = eng.submit(list(range(1, 20)), max_new_tokens=2)
    eng.step()
    mid = eng.stats()
    assert mid["admitted"] == 1 and mid["queue_depth"] == 1
    assert mid["first_tokens"] == 0           # 19 tokens: two spans
    while eng.step():
        pass
    assert len(first.tokens()) == 8 and len(held.tokens()) == 2
    st = eng.stats()
    assert st["admitted"] == 2 and st["first_tokens"] == 2
    slots = [r for r in events.drain() if r.get("state") == "RUNNING"
             and r["name"] == "engine.slot"]
    waits = [s["attrs"]["queue_wait_ms"] for s in slots]
    assert len(waits) == 2 and waits[1] > waits[0] >= 0
    # the counter is the sum of what the spans carry
    assert st["queue_wait_s"] * 1e3 == pytest.approx(sum(waits), abs=2e-3)
    # the held request waited for the whole of the first one, and that
    # is nearly all of its time to a first token
    assert first.first_token_t - held.submitted_t < waits[1] / 1e3 \
        < held.first_token_t - held.submitted_t
    # and TTFT at the replica is queue wait + prefill span, request by
    # request and so in sum
    ttft = sum(h.first_token_t - h.submitted_t for h in (first, held))
    assert st["queue_wait_s"] + st["prefill_span_s"] == pytest.approx(
        ttft, abs=1e-6)
    assert st["prefill_span_s"] > 0


def test_on_step_is_handed_what_the_gauges_read():
    from ray_tpu.inference.api import _EngineMetrics
    eng = _engine(n_slots=1)
    got = []
    gauges = _EngineMetrics()

    def on_step(stats):
        got.append(dict(stats))
        gauges.on_step(stats)

    eng.on_step = on_step
    handles = [eng.submit([1, 2, 3], max_new_tokens=3) for _ in range(3)]
    eng.step()
    assert got == [{"slots_occupied": 1, "queue_depth": 2}]
    snap = {m["name"]: m for m in metrics_mod.registry_snapshot()}
    assert snap["serve_llm_queue_depth"]["samples"][0][1] == 2
    assert snap["serve_llm_slot_occupancy"]["samples"][0][1] == 1
    while eng.step():
        pass
    assert all(len(h.tokens()) == 3 for h in handles)
    assert got[-1] == {"slots_occupied": 0, "queue_depth": 0}
    assert len(got) == eng.steps
    # stats() itself keeps every key it had, and gains the four counters
    assert {"n_slots", "slots_occupied", "queue_depth", "steps",
            "fused_steps", "tokens_generated", "kv_pool_bytes", "admitted",
            "queue_wait_s", "first_tokens", "prefill_span_s"} \
        <= set(eng.stats())


# ------------------------------------------------- the programs' own text
def _decode_text(eng):
    return eng._decode_fn.lower(
        eng.params, *eng._slots.pools(), eng._carry,
        np.zeros((eng.config.n_slots,), np.int32)).as_text()


def _train_text(_):
    import optax

    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_fns
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, n_kv_heads=2, d_ff=64,
                            max_seq_len=32)
    init, step, _ = make_train_fns(TransformerLM(cfg), optax.adam(1e-3),
                                   make_mesh(MeshConfig(data=1, fsdp=1),
                                             devices=jax.devices()[:1]),
                                   batch_shape=(2, 16))
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    return jax.jit(step).lower(
        state, jax.ShapeDtypeStruct((2, 16), jnp.int32)).as_text()


INDEXER = dict(index_heads=2, index_head_dim=16, index_topk=16)
# the other four kinds a cell runs, each at its own model test's widths and
# at the depth that holds every kind of layer it has once
MOE = dict(n_experts=4, expert_top_k=2, capacity_factor=2.0)   # Mixtral's
_KINDS = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
              dtype=jnp.float32, remat=False, scan_layers=False)
BLK_LIN = dict(_KINDS, mixer_kinds=("lin", "blk"), qk_norm=True,
               blk_size=4, blk_kernel=2, blk_stride=1, blk_window=8,
               blk_topk=6, attn_rope=False, out_gate=True,
               scale_emb=12, residual_scale=0.25, logit_scale=0.25)
HYB = dict(_KINDS, n_heads=5, n_kv_heads=1, mixer_kinds=("hyb", "hyb"),
           ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
           key_mult=0.011, ssm_in_mult=0.25, ssm_out_mult=0.088,
           ssm_mults=(0.35, 0.25, 0.18, 0.5, 0.35), mlp_mults=(0.18, 0.011))
WIN_ATT = dict(_KINDS, n_layers=3, n_heads=6, logits_fp32=True,
               mixer_kinds=("win", "att", "win"), window=16, win_ring=32,
               n_experts=16, capacity_factor=16.0, router="sigmoid",
               route_scale=2.448, expert_d_ff=24, n_shared_experts=1,
               n_dense_layers=1, sandwich_norm=True, qk_norm=True,
               attn_rope=False, out_gate=True, scale_emb=8.0)


@pytest.mark.parametrize("model,text,digest", [
    ({}, _decode_text, "84285cf3fb9f06eb"),
    ({}, _tile_text, "fd9f5c4aa916b342"),
    (INDEXER, _decode_text, "8702c029e6542675"),
    (INDEXER, _tile_text, "2d7af74232ada8e8"),
    (MOE, _decode_text, "f059ce00b6dd0b8c"),
    (MOE, _tile_text, "8c13b7ec957e1f51"),
    (BLK_LIN, _decode_text, "f0e47b713fd975e2"),
    (BLK_LIN, _tile_text, "0a6a860030ec656f"),
    (HYB, _decode_text, "dc36b41b20baaf12"),
    (HYB, _tile_text, "0a4b070ac3459947"),
    (WIN_ATT, _decode_text, "b09edbdff40eaf1e"),
    (WIN_ATT, _tile_text, "961b4ef1e9971aa7"),
    (None, _train_text, "8aecbfdae32759c2")],
    ids=["dense_decode", "dense_tile_with_rows", "indexer_decode",
         "indexer_tile", "moe_decode", "moe_tile_with_rows",
         "blk_lin_decode", "blk_lin_tile_with_rows", "hyb_decode",
         "hyb_tile_with_rows", "win_att_decode", "win_att_tile_with_rows",
         "train_step"])
def test_the_step_programs_are_the_parents(model, text, digest):
    """The lowered text of the decode program and of the tile program (with
    the riding rows, and an indexer model's without) for every kind of
    model a cell runs, and of a training step. The training step's digest
    is PR 41's tree's (read there by this same function, before the step
    was told by phase): the marks are the host's, no program recompiles
    for them and set-up has no reason to move. The four engine programs'
    were re-pinned once, on PR 43's final tree: that PR moved the slots'
    carry (lengths, last tokens, temperatures, the key) onto the device as
    one array the programs take and hand back advanced, packed the host's
    arguments into one array and made the tile's key of the carry's and a
    count, so every engine program's signature and last few ops changed;
    what they compute of the model did not (tests/test_step_order.py holds
    their greedy tokens to the parent's). The two TILE programs' were
    re-pinned on PR 45's final tree: the tile names the rows it samples
    (the prompt's would-be next token and the riding rows) and the cached
    forward gathers them before the final norm and the head, so the head's
    dot has 1 + S rows (1 for the indexer's model) where it had T + S.
    Both decode programs and the training step passed with the digests
    they had: a cached call that names no rows is the program it was, and
    the `lm_head` scope is metadata the lowered text does not print. The
    two programs that hold a DENSE model's decode rows were re-pinned on
    PR 47's final tree, the decode program and the tile program with the
    riding rows: such a row attends its slot's live key blocks in the
    pools where they lie (`_row_attention`) and no longer a layer sliced
    out of the pool; tests/test_step_order.py holds their greedy tokens to
    the parent's. The indexer's two programs and the training step pass
    with the digests they had. The eight digests of the other four kinds
    (all experts held, "blk" + "lin", "hyb", "win" + "att" with the
    sigmoid router) were read by this function on PR 48's tree, at the
    start of PR 49 and before a line of it was written. PR 49 removed the
    speculative draft's step (and its pinned verify program with it) and
    moved the rows' counters into the model layer: all thirteen passed
    unchanged on its final tree, so it recompiled nothing. The four
    programs of the two kinds whose experts' capacity is the group's whole
    length ("moe": 4 experts, top-2 at capacity_factor 2; "win" + "att":
    16 at 16) were re-pinned on PR 55's final tree: the engine counts the
    expert layers' rows for such a model (a row no request owns is routed
    nowhere, and the carry holds the counts), and where the group is long
    enough the layer sorts its picks and runs the grouped form
    (`models/moe.py` `takes_grouped`; here its XLA twin);
    tests/test_step_order.py holds their greedy tokens to the parent's and
    tests/test_grouped_moe.py the form to the dense dispatch. The other
    nine passed with the digests they had."""
    eng = None if model is None else _engine(**model)
    got = hashlib.sha256(text(eng).encode()).hexdigest()[:16]
    assert got == digest
