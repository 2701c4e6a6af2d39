"""rtlint — the runtime-aware static analysis gate (ISSUE 8).

Three tiers here:

- rule semantics against the fixture corpus (`tests/lint_fixtures/`):
  every rule detects its bad fixtures and stays silent on its clean
  fixture; suppressions and the baseline behave as documented;
- the SELF-GATE: `ray_tpu lint ray_tpu/ --format json` over the real
  package exits 0 with zero unsuppressed findings, in under 10 s (the
  CI wall-clock guard);
- the compile-once invariant covered by BOTH layers: RT002 flags the
  retrace-inducing scalar pattern statically, and the same class of
  bug monkeypatched into the live decode step is caught dynamically by
  `decode_compile_count` — the two layers watch the same failure.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from ray_tpu.devtools.lint import run_lint
from ray_tpu.devtools.lint.baseline import Baseline
from ray_tpu.devtools.lint.config import LintConfig, load_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")


def lint_fixture(*names, enable=None):
    paths = [os.path.join(FIXTURES, n) for n in names]
    return run_lint(paths, config=LintConfig(root=REPO), enable=enable,
                    use_baseline=False)


def rules_hit(result):
    return sorted({f.rule for f in result.findings})


# ------------------------------------------------------------- rule corpus

@pytest.mark.parametrize("bad,rule,min_hits", [
    ("rt001_bad_sleep.py", "RT001", 3),
    ("rt001_bad_handler.py", "RT001", 3),
    ("rt002_bad_coerce.py", "RT002", 3),
    ("rt002_bad_spec_accept.py", "RT002", 3),
    ("rt002_bad_donate.py", "RT002", 2),
    ("rt002_bad_donate_apply.py", "RT002", 2),
    ("rt003_bad_unlocked.py", "RT003", 3),
    ("rt003_bad_wrong_lock.py", "RT003", 1),
    ("_private/rt004_bad_daemon.py", "RT004", 2),
    ("rt005_bad_returns.py", "RT005", 4),
])
def test_bad_fixture_detected(bad, rule, min_hits):
    r = lint_fixture(bad)
    hits = [f for f in r.findings if f.rule == rule]
    assert len(hits) >= min_hits, [f.format() for f in r.findings]
    # findings carry usable locations
    assert all(f.line > 0 and f.path.endswith(bad.split("/")[-1])
               for f in hits)


@pytest.mark.parametrize("clean", [
    "rt001_clean.py", "rt002_clean.py", "rt003_clean.py",
    "_private/rt004_clean.py", "rt005_clean.py",
])
def test_clean_fixture_not_flagged(clean):
    r = lint_fixture(clean)
    assert r.findings == [], [f.format() for f in r.findings]


def test_rt004_scoped_to_private_paths(tmp_path):
    """The same daemon-swallow pattern outside a _private/ path is out
    of RT004's scope (the rule's path_filter)."""
    src = open(os.path.join(FIXTURES, "_private",
                            "rt004_bad_daemon.py")).read()
    p = tmp_path / "userland.py"
    p.write_text(src)
    r = run_lint([str(p)], config=LintConfig(root=str(tmp_path)),
                 use_baseline=False)
    assert not [f for f in r.findings if f.rule == "RT004"]


def test_rt002_branch_allows_is_none():
    src = textwrap.dedent("""
        import jax
        @jax.jit
        def f(x, temp):
            if temp is None:          # trace-time Python: allowed
                return x
            return x * temp
    """)
    import ast as ast_mod
    from ray_tpu.devtools.lint.registry import FileContext
    from ray_tpu.devtools.lint.rules.rt002_jit_retrace import JitRetraceRule
    ctx = FileContext("mod.py", src, ast_mod.parse(src))
    assert list(JitRetraceRule().check(ctx)) == []


# ---------------------------------------------------- suppression semantics

def test_suppressions_trailing_standalone_and_def_scope():
    r = lint_fixture("suppressed.py")
    assert r.findings == [], [f.format() for f in r.findings]
    assert r.suppressed == 4      # 2 inline + 2 under the def-line pragma


def test_suppression_only_silences_named_rule():
    r = lint_fixture("suppressed.py", enable=["RT001"])
    assert r.findings == []
    # a pragma naming RT001 must not hide other rules on the same line —
    # check the suppression map directly
    from ray_tpu.devtools.lint.suppress import (is_suppressed,
                                                parse_suppressions)
    src = open(os.path.join(FIXTURES, "suppressed.py")).read()
    per_line, file_wide = parse_suppressions(src)
    some_line = next(iter(per_line))
    assert is_suppressed("RT001", some_line, [], per_line, file_wide)
    assert not is_suppressed("RT004", some_line, [], per_line, file_wide)


# ----------------------------------------------------------- baseline gate

def test_baseline_passes_known_and_fails_new(tmp_path):
    # without a baseline the legacy finding fails the gate
    r = lint_fixture("baselined.py")
    assert len(r.findings) == 1 and r.findings[0].rule == "RT001"

    # register it with a justification -> gate passes, finding reported
    # as baselined with the justification attached
    bpath = tmp_path / "bl.json"
    bl = Baseline()
    bl.update(r.findings, str(bpath))
    doc = json.loads(bpath.read_text())
    doc["entries"][0]["justification"] = "legacy sleep; tracked in #42"
    bpath.write_text(json.dumps(doc))

    r2 = run_lint([os.path.join(FIXTURES, "baselined.py")],
                  config=LintConfig(root=REPO),
                  baseline_path=str(bpath))
    assert r2.ok and r2.findings == []
    assert len(r2.baselined) == 1
    assert r2.baselined[0].justification == "legacy sleep; tracked in #42"

    # a NEW finding alongside the baselined one still fails
    r3 = run_lint([os.path.join(FIXTURES, "baselined.py"),
                   os.path.join(FIXTURES, "rt001_bad_sleep.py")],
                  config=LintConfig(root=REPO), baseline_path=str(bpath))
    assert not r3.ok and len(r3.findings) >= 3


def test_baseline_update_preserves_justifications_and_reports_stale(
        tmp_path):
    r = lint_fixture("baselined.py")
    bpath = tmp_path / "bl.json"
    Baseline().update(r.findings, str(bpath))
    doc = json.loads(bpath.read_text())
    doc["entries"][0]["justification"] = "keep me"
    # plus a stale entry for code that no longer exists
    doc["entries"].append({"fingerprint": "feedfacedeadbeef",
                           "rule": "RT001", "path": "gone.py",
                           "symbol": "x", "snippet": "gone()",
                           "justification": "obsolete"})
    bpath.write_text(json.dumps(doc))

    r2 = run_lint([os.path.join(FIXTURES, "baselined.py")],
                  config=LintConfig(root=REPO), baseline_path=str(bpath))
    assert r2.stale_baseline == ["feedfacedeadbeef"]

    bl = Baseline.load(str(bpath))
    bl.update(r2.findings + r2.baselined, str(bpath))
    doc2 = json.loads(bpath.read_text())
    assert len(doc2["entries"]) == 1            # stale entry pruned
    assert doc2["entries"][0]["justification"] == "keep me"

    # fingerprints survive the finding moving to another line (same
    # repo-relative path, edits above the finding)
    src = open(os.path.join(FIXTURES, "baselined.py")).read()
    moved = tmp_path / "tests" / "lint_fixtures" / "baselined.py"
    moved.parent.mkdir(parents=True)
    moved.write_text("# pushed down\n\n" + src)
    r3 = run_lint([str(moved)], config=LintConfig(root=str(tmp_path)),
                  use_baseline=False)
    assert r3.findings[0].line != r2.baselined[0].line
    # same (rule, path, symbol, snippet) -> same fingerprint
    assert r3.findings[0].fingerprint == r2.baselined[0].fingerprint


# ------------------------------------------------------- config resolution

def test_tool_rtlint_config_block(tmp_path):
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [project]
        name = "x"

        [tool.rtlint]
        paths = ["pkg"]
        exclude = ["__pycache__", "pkg/vendor"]
        enable = ["RT001", "RT004"]
        baseline = "custom-baseline.json"
    """))
    cfg = load_config(str(tmp_path))
    assert cfg.paths == ["pkg"]
    assert cfg.enable == ["RT001", "RT004"]
    assert cfg.exclude[-1] == "pkg/vendor"
    assert cfg.baseline_path == str(tmp_path / "custom-baseline.json")

    # enabled-rule subset is honored end to end
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import time\nasync def f():\n    time.sleep(1)\n")
    r = run_lint(config=load_config(str(tmp_path)), use_baseline=False)
    assert rules_hit(r) == ["RT001"]
    assert r.rules_run == ["RT001", "RT004"]


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="unknown rule"):
        run_lint([FIXTURES], config=LintConfig(root=REPO),
                 enable=["RT999"], use_baseline=False)


# ------------------------------------------------------------ CLI contract

def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "lint", *args],
        capture_output=True, text=True, cwd=cwd, timeout=120)


def test_cli_exit_codes_and_json():
    bad = _cli(os.path.join("tests", "lint_fixtures",
                            "rt001_bad_sleep.py"), "--format", "json")
    assert bad.returncode == 1, bad.stderr[-1000:]
    doc = json.loads(bad.stdout)
    assert not doc["ok"] and len(doc["findings"]) >= 3
    assert {"rule", "path", "line", "message", "fingerprint"} <= \
        set(doc["findings"][0])

    clean = _cli(os.path.join("tests", "lint_fixtures", "rt001_clean.py"))
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "0 new finding(s)" in clean.stdout


def test_cli_self_gate_package_clean_and_fast():
    """THE acceptance gate: `ray_tpu lint ray_tpu/ --format json` over
    the whole package — zero unsuppressed findings, exit 0, < 10 s of
    the lint's own CPU time (tier-1 box guard; the wall clock of a box
    that runs six test workers measures the neighbours)."""
    t0 = os.times()
    r = _cli("ray_tpu", "--format", "json")
    t1 = os.times()
    cpu = (t1.children_user + t1.children_system
           - t0.children_user - t0.children_system)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    doc = json.loads(r.stdout)
    assert doc["ok"] and doc["findings"] == []
    assert doc["files_scanned"] > 100          # really saw the package
    assert doc["errors"] == []
    # every baselined finding carries a real justification
    for f in doc["baselined"]:
        assert f.get("justification"), f
        assert "TODO" not in f["justification"], f
    assert cpu < 10.0, f"lint self-gate took {cpu:.1f}s CPU (budget 10s)"


# --------------------------------------------------- off_loop marker plumb

def test_off_loop_marker_is_pure_annotation():
    from ray_tpu._private.markers import off_loop

    class C:
        @off_loop(lock="_mu")
        def m(self):
            return 41

    assert C().m() == 41
    assert C.m.__rt_off_loop__ == {"lock": "_mu"}


def test_arena_client_methods_are_marked():
    from ray_tpu._private.object_store import ObjectStoreClient
    for name in ("create", "get", "put_bytes", "_release", "close"):
        fn = getattr(ObjectStoreClient, name)
        assert getattr(fn, "__rt_off_loop__", None) == \
            {"lock": "_pins_lock"}, name


# ------------------------------------- compile-once invariant, both layers

_RETRACE_SNIPPET = textwrap.dedent("""
    import jax

    def build(model):
        def decode(params, pk, pv, lengths, toks, rng, temps):
            cur = int(lengths)         # host coercion of traced state
            if lengths > 0:            # data-dependent Python branch
                toks = toks + cur
            return toks
        return jax.jit(decode)
""")


def test_compile_once_static_layer_flags_retrace_pattern(tmp_path):
    p = tmp_path / "decode_like.py"
    p.write_text(_RETRACE_SNIPPET)
    r = run_lint([str(p)], config=LintConfig(root=str(tmp_path)),
                 use_baseline=False)
    msgs = [f.message for f in r.findings if f.rule == "RT002"]
    assert any("concretizes" in m for m in msgs), msgs
    assert any("branch" in m for m in msgs), msgs


def test_compile_once_dynamic_layer_catches_retrace():
    """The runtime side of the same invariant: a retrace-inducing
    wrapper monkeypatched into the decode step drives
    decode_compile_count past 1 within a few steps — the dynamic check
    (engine.compile instants + the ==1 assertions in
    test_inference_engine.py) covers exactly the failure RT002 flags
    statically."""
    jax = pytest.importorskip("jax")
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    import numpy as np

    from ray_tpu.inference import EngineConfig, InferenceEngine
    from ray_tpu.models.transformer import TransformerConfig, TransformerLM
    import jax.numpy as jnp

    tcfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)
    model = TransformerLM(tcfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    eng = InferenceEngine(model, params, EngineConfig(
        n_slots=2, max_len=32, prefill_chunk=4, prefill_budget=8))

    # healthy engine: decode compiles exactly once over several steps
    h = eng.submit(np.arange(1, 6), max_new_tokens=8)
    for _ in range(4):
        eng.step()
    assert eng.decode_compile_count == 1

    # the bug RT002 models: a host-coerced scalar folded into the
    # program's cache identity — here the current max sequence length
    # rides in as a STATIC arg, so every step's new value is a cache
    # miss that re-traces the decode body (and bumps the trace counter)
    raw = eng._decode_fn.__wrapped__

    def decode_with_scalar(cur_len, *args):
        return raw(*args)

    bad_jit = jax.jit(decode_with_scalar, static_argnums=(0,))

    def retracing_decode(*args):
        cur_len = int(np.asarray(eng._lengths).max())   # the coercion
        return bad_jit(cur_len, *args)

    eng._decode_fn = retracing_decode
    before = eng.decode_compile_count
    for _ in range(3):
        eng.step()
    assert h is not None
    assert eng.decode_compile_count >= before + 2, (
        "dynamic layer failed to observe the retrace",
        eng.decode_compile_count)
