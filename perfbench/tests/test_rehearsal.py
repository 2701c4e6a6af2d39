"""The command itself, on the CPU: the rehearsal mode runs a tiny cell
end to end and can never print a metric; the measuring path fails where
there is no chip."""
import json
import os
import subprocess
import sys

import pytest

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


def test_the_measuring_path_fails_without_a_chip():
    p = _run("--workload", "mistral-7b.chat-steady", "--seed", "1",
             "--seconds", "3", "--trace", "0")
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "TPU=0" in p.stderr
