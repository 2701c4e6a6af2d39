"""Tests of the harness's own rule (tests/conftest.py): what a test file
starts, sets or forks ends with the file, and every test has a time
limit of its own."""

import os
import subprocess
import sys
import textwrap

import pytest

from ray_tpu._private.config import cfg
from ray_tpu._private.proc_util import find_session_processes
from tests import conftest as harness


def _sleeper(marker):
    """A child that looks like a leaked daemon: `ray_tpu` in its cmdline,
    the marker in its environment."""
    return subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(120)", "ray_tpu"],
        env={**os.environ, "RAY_TPU_TEST_SESSION": marker})


def test_leftover_process_is_killed_and_charged_to_its_file():
    marker = harness.file_marker("test_some_file.py")
    child = _sleeper(marker)
    try:
        assert list(find_session_processes(marker)) == [child.pid]
        leaked = harness.end_of_file(marker, dict(os.environ), grace_s=0.5)
        assert len(leaked) == 1
        assert f"pid {child.pid}" in leaked[0] and "ray_tpu" in leaked[0]
        assert child.wait(10) == -9
        assert harness.end_processes(marker, grace_s=0) == []
    finally:
        child.kill()


def test_another_workers_file_is_never_touched():
    other = f"{harness._BASE_MARKER}.gw99.test_some_file.py"
    assert not other.startswith(harness.WORKER_MARKER)
    child = _sleeper(other)
    try:
        mine = harness.file_marker("test_some_file.py")
        assert harness.end_of_file(mine, dict(os.environ), grace_s=0) == []
        assert harness.end_processes(harness.WORKER_MARKER,
                                     grace_s=0) == []
        assert child.poll() is None
    finally:
        child.kill()
        child.wait(10)


def test_environment_and_configuration_are_back_at_the_files_end():
    before = dict(os.environ)
    default = cfg.launch_trace_enabled
    os.environ["RAY_TPU_HARNESS_RULE_ADDED"] = "1"
    os.environ["RAY_TPU_TEST_SESSION"] = "changed"
    had_path = os.environ.pop("PATH")
    cfg.set("launch_trace_enabled", not default)     # as a head's snapshot
    assert harness.end_of_file("no-such-marker", before, grace_s=0) == []
    assert dict(os.environ) == before and os.environ["PATH"] == had_path
    assert cfg.launch_trace_enabled is default
    os.environ["RAY_TPU_LAUNCH_TRACE_ENABLED"] = str(int(not default))
    try:
        assert cfg.launch_trace_enabled is not default    # env is seen again
    finally:
        del os.environ["RAY_TPU_LAUNCH_TRACE_ENABLED"]


_SUB_TESTS = textwrap.dedent("""
    import threading
    import time

    import pytest


    @pytest.mark.time_limit(1)
    def test_sleeps_past_its_limit():
        threading.Thread(target=time.sleep, args=(5,), name="bystander",
                         daemon=True).start()
        time.sleep(30)


    def test_the_next_one_runs():
        pass
""")


def _sub_pytest(tmp_path, *args):
    """A pytest run of tmp_path's files under this conftest.py, loaded
    as a plugin: the files live outside tests/, where a cut run could not
    leave them to be collected."""
    repo = os.path.dirname(os.path.dirname(harness.__file__))
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_TEST_SESSION", "PYTEST_XDIST_WORKER")}
    r = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q",
         "-c", os.path.join(repo, "pyproject.toml"), "-p", "tests.conftest",
         "-p", "no:cacheprovider", "-p", "no:randomly", *args],
        capture_output=True, text=True, timeout=100, cwd=repo, env=env)
    return r.returncode, r.stdout + r.stderr


@pytest.mark.time_limit(120)
def test_a_test_past_its_limit_fails_with_stacks_and_the_next_runs(tmp_path):
    (tmp_path / "test_sub.py").write_text(_SUB_TESTS)
    rc, out = _sub_pytest(tmp_path, "-p", "no:xdist")
    assert rc == 1, out
    assert "1 failed, 1 passed" in out, out
    assert "passed its 1 s limit in call" in out, out
    # every thread's stack: the sleeping test's own and the bystander's
    assert "test_sleeps_past_its_limit" in out and "Thread 0x" in out, out


_SUB_STUCK = textwrap.dedent("""
    import signal
    import time

    import pytest

    import tests.conftest as harness

    harness.HARD_GRACE_S = 2.0


    @pytest.mark.time_limit(1)
    def test_stuck_where_no_signal_is_served():
        signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
        time.sleep(60)


    def test_the_rest_of_the_file_runs():
        pass
""")


@pytest.mark.time_limit(120)
def test_a_test_no_signal_reaches_costs_its_worker_once_not_the_run(tmp_path):
    (tmp_path / "test_stuck.py").write_text(_SUB_STUCK)
    (tmp_path / "test_other.py").write_text("def test_other():\n    pass\n")
    rc, out = _sub_pytest(tmp_path, "-p", "xdist", "-n", "2",
                          "--dist", "loadfile")
    assert rc == 1, out
    assert "Timeout (0:00:03)!" in out and "node down" in out, out
    # loadfile hands the file to a new worker with the stuck test first:
    # it is failed there, not run (and not timed out) a second time
    assert out.count("node down") == 1, out
    assert "not run again" in out, out
    assert "1 failed, 2 passed, 1 error" in out, out
