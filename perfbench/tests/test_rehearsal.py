"""The command itself, on the CPU: the rehearsal mode runs a tiny cell
end to end and can never print a metric; the measuring path fails where
there is no chip."""
import json
import os
import subprocess
import sys

import pytest
from later_pr import add_later_pr, snapshot

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_root")


def _run(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell", ["tiny-mistral.open", "tiny-mixtral.closed",
                                  "tiny-mistral-train.train"])
def test_rehearsal_runs_the_cell_and_prints_no_metric(cell):
    p = _run("--root", FIXTURE, "--rehearse", "--workload", cell,
             "--seed", "3000000001", "--seconds", "3", "--trace", "1")
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] is True, last
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "device: cpu" in p.stderr


def test_a_later_prs_family_rehearses_from_new_files_alone(tmp_path):
    """A family the harness has never seen (another model class, a leaf and
    key names that `mistral` refuses, its own reference) serves a cell to
    `correct`; the bias on its logits (standard deviation 1) is known only
    to its own reference, which is what scored the cell's tokens at the
    lengths the mix states; a `mistral` cell beside it still runs."""
    root = str(tmp_path / "root")
    before = add_later_pr(root)
    after = snapshot(root)
    lasts = {}
    for cell in ("tiny-other.burst", "tiny-mistral.open"):
        p = _run("--root", root, "--rehearse", "--workload", cell,
                 "--seed", "3000000002", "--seconds", "3", "--trace", "0")
        assert p.returncode == 0, p.stderr[-3000:]
        lasts[cell] = json.loads(p.stdout.strip().splitlines()[-1])
        assert lasts[cell]["correct"] is True, lasts[cell]
        assert lasts[cell]["attempted"] > 0 and lasts[cell]["failed"] == 0
    ref = lasts["tiny-other.burst"]["reference"]
    assert ref["n_tokens"] == 8 + 6 and ref["logit_std"] > 0.5
    assert ref["share_within_gap"] >= 0.9
    ref = lasts["tiny-mistral.open"]["reference"]
    assert ref["n_tokens"] == 48 and ref["logit_std"] < 0.5
    assert snapshot(root) == after           # a run writes nothing there
    assert all(after[p] == data for p, data in before.items()
               if not p.endswith("BENCHMARK.json"))


def test_the_measuring_path_fails_without_a_chip():
    p = _run("--workload", "mistral-7b.chat-steady", "--seed", "1",
             "--seconds", "3", "--trace", "0")
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "TPU=0" in p.stderr
