"""Test env: force an 8-device virtual CPU mesh (multi-chip sharding is
tested hermetically on the CPU; what only a chip can show is compiled for a
described chip in tests/test_chip_compile.py and run on one by
chip_smoke.py).

Set via jax.config (not env vars): pytest plugins may import jax before this
conftest runs, but the backend only initializes on first device use, so the
config route still wins."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")   # workers inherit it
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

try:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
except Exception:   # backend already initialized (env vars took effect)
    pass

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import multiprocessing  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
import uuid  # noqa: E402

# The run's base marker: set once, in the xdist controller (or the one
# pytest process), and inherited by every worker. A test FILE runs under
# a marker of its own built on it (the_file_ends_with_the_file below), so
# whatever is alive with that marker when the file ends is that file's
# leak, under any --dist and with or without xdist.
_BASE_MARKER = os.environ.setdefault("RAY_TPU_TEST_SESSION", uuid.uuid4().hex)
_WORKER = os.environ.get("PYTEST_XDIST_WORKER", "main")

import pytest  # noqa: E402

# Two tiers (suite wall-clock grows ~6 min/round; the full matrix is for
# rounds/CI, the fast tier for inner-loop dev):
#   fast:  python -m pytest tests/ -m 'not slow'   (~1/3 of the time)
#   full:  python -m pytest tests/
_SLOW_FILES = {
    "test_chaos.py", "test_cluster_launcher.py", "test_data_shuffle.py",
    "test_data_ingest.py", "test_gcs_ft.py", "test_jax_distributed.py",
    "test_multi_node.py", "test_object_transfer.py",
    "test_rl_regression.py", "test_rl_algos.py", "test_rl_multi_agent.py",
    "test_runtime_env_pip.py", "test_serve_harden.py", "test_serve.py",
    "test_slice_gang.py", "test_train_e2e.py", "test_tune.py",
    "test_view_sync.py", "test_sharded_checkpoint.py",
}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.path.name in _SLOW_FILES:
            item.add_marker(pytest.mark.slow)


# ------------------------------------------------------------------------
# The rule: what a test file starts, sets or forks ends with the file.
# ------------------------------------------------------------------------

# Prefix of every marker this pytest process hands to its files
# (find_session_processes matches by prefix; the trailing dot keeps gw1
# off gw10).
WORKER_MARKER = f"{_BASE_MARKER}.{_WORKER}."
STRAYS_LOG = "/tmp/raytpu/hygiene_strays.log"


def file_marker(filename: str) -> str:
    return WORKER_MARKER + filename


def describe_process(pid: int) -> str:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode()[:160]
    except OSError:
        return "<gone>"


def note_strays(who: str, lines: list) -> str:
    """Keep the evidence (a failure's detail is cut short under -q);
    returns it as the text to show."""
    detail = "\n  ".join(lines)
    try:
        with open(STRAYS_LOG, "a") as f:
            f.write(f"{who} at {time.time()}:\n  {detail}\n")
    except OSError:
        pass
    return detail


def end_processes(marker: str, grace_s: float) -> list:
    """Wait a bounded time for the ray_tpu processes carrying `marker`
    to go, kill the ones that stayed, and return a line for each
    (empty: nothing leaked). Teardown is asynchronous (SIGTERM -> worker
    reap, the node manager's bounded GCS-reconnect exit), hence the
    grace."""
    from ray_tpu._private.proc_util import find_session_processes
    deadline = time.monotonic() + grace_s
    while True:
        strays = list(find_session_processes(marker))
        if not strays or time.monotonic() >= deadline:
            break
        time.sleep(0.2)
    leaked = [f"pid {p}: {describe_process(p)}" for p in strays]
    for p in strays:
        try:
            os.kill(p, signal.SIGKILL)
        except OSError:
            pass
    return leaked


def end_children(grace_s: float = 5.0) -> list:
    """Join (bounded) or kill this process's multiprocessing children;
    a line for each that had to be killed."""
    killed = []
    deadline = time.monotonic() + grace_s
    for child in multiprocessing.active_children():
        child.join(max(0.0, deadline - time.monotonic()))
        if child.is_alive():
            killed.append(f"pid {child.pid}: multiprocessing child "
                          f"{child.name}")
            child.kill()
            child.join(5)
    return killed


def restore_environ(before: dict) -> None:
    for k in set(os.environ) - set(before):
        del os.environ[k]
    for k, v in before.items():
        if os.environ.get(k) != v:
            os.environ[k] = v


def reset_process_state() -> None:
    """Disconnect this process from any cluster and drop what it cached
    of one: a driver that has connected keeps the head's whole config
    snapshot as explicit values (which outrank RAY_TPU_* variables), so
    the next file's setenv would go unseen."""
    if "ray_tpu.serve.api" in sys.modules:
        from ray_tpu.serve import api as serve_api
        if serve_api._controller_handle is not None:
            serve_api.shutdown()
    import ray_tpu
    ray_tpu.shutdown()
    from ray_tpu._private import blackbox
    from ray_tpu._private.config import cfg
    cfg.reset()
    blackbox.reset()


def end_of_file(marker: str, environ_before: dict,
                grace_s: float = 30.0) -> list:
    """Everything the rule does when a file ends; returns what leaked."""
    try:
        reset_process_state()
    finally:
        leaked = end_processes(marker, grace_s) + end_children()
        restore_environ(environ_before)
    return leaked


@pytest.fixture(scope="module", autouse=True)
def the_file_ends_with_the_file(request):
    marker = file_marker(request.path.name)
    before = dict(os.environ)
    os.environ["RAY_TPU_TEST_SESSION"] = marker
    yield marker
    leaked = end_of_file(marker, before)
    if leaked:
        pytest.fail(f"{request.path.name} left {len(leaked)} process(es) "
                    "behind (killed now):\n  "
                    + note_strays(marker, leaked), pytrace=False)


@pytest.fixture(scope="module")
def ray_start(request, the_file_ends_with_the_file):
    """One local cluster for the file, sized by its module-level
    RAY_START = dict(...) (ray_tpu.init's keywords; default num_cpus=4).
    The rule above shuts it down."""
    import ray_tpu
    kwargs = getattr(request.module, "RAY_START", None) or {"num_cpus": 4}
    return ray_tpu.init(**kwargs)


# ------------------------------------------------------------------------
# Every test has a time limit of its own (no pytest-timeout here).
# ------------------------------------------------------------------------
# One number for the suite: the driver's machine is several times slower
# than a builder's and the whole run has 1,470 s. A test that truly needs
# more says @pytest.mark.time_limit(seconds). The limit holds for each of
# setup, call and teardown. SIGALRM interrupts the worker's main thread
# (tests run there under xdist too) and the phase fails with every
# thread's stack; a test stuck where no signal is served (C code) is
# ended HARD_GRACE_S later by faulthandler's watchdog, which costs that
# test its worker and not the run.
TIME_LIMIT_S = 300.0
HARD_GRACE_S = 60.0

_real_stderr = None


def pytest_configure(config):
    global _real_stderr
    # fd 2 as it is while capture is off: where the watchdog writes
    capman = config.pluginmanager.getplugin("capturemanager")
    with (capman.global_and_fixture_disabled() if capman
          else contextlib.nullcontext()):
        _real_stderr = os.fdopen(os.dup(2), "w")


def _all_stacks() -> str:
    with tempfile.TemporaryFile("w+") as f:
        faulthandler.dump_traceback(file=f, all_threads=True)
        f.seek(0)
        return f.read()


def _under_limit(item, phase):
    if threading.current_thread() is not threading.main_thread():
        yield       # no signal can be taken here
        return
    mark = item.get_closest_marker("time_limit")
    limit = float(mark.args[0]) if mark else TIME_LIMIT_S

    def on_alarm(signum, frame):
        pytest.fail(f"{item.nodeid} passed its {limit:g} s limit in "
                    f"{phase}; every thread's stack:\n{_all_stacks()}",
                    pytrace=False)

    previous = signal.signal(signal.SIGALRM, on_alarm)
    faulthandler.dump_traceback_later(limit + HARD_GRACE_S, exit=True,
                                      file=_real_stderr or sys.__stderr__)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)


# xdist's loadfile scheduler hands a file whose worker died to a new
# worker WITH the test that killed it, again and again. So each worker
# notes the test it is in, and a test found in a dead worker's note is
# failed, not run: the watchdog then costs one test once.
_NOTES = os.path.join(tempfile.gettempdir(), f"ray_tpu_tests_{_BASE_MARKER}")


def _ended_a_worker(nodeid: str) -> bool:
    for name in os.listdir(_NOTES):
        if name != _WORKER:
            try:
                with open(os.path.join(_NOTES, name)) as f:
                    if f.read() == nodeid:
                        return True
            except OSError:     # its worker has just finished the test
                pass
    return False


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    os.makedirs(_NOTES, exist_ok=True)
    note = os.path.join(_NOTES, _WORKER)
    with open(note, "w") as f:
        f.write(item.nodeid)
    yield
    os.unlink(note)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    if _ended_a_worker(item.nodeid):
        pytest.fail(f"{item.nodeid} ended the worker that ran it (the "
                    "watchdog's stacks are in the log, or it crashed); "
                    "not run again", pytrace=False)
    yield from _under_limit(item, "setup")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _under_limit(item, "call")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    yield from _under_limit(item, "teardown")


def pytest_sessionfinish(session):
    """After the last worker: nothing with the run's marker is owned by
    anyone any more (a worker that died took its file's finalizer with
    it), so what is left is ended here and said aloud."""
    if "PYTEST_XDIST_WORKER" in os.environ:
        return
    shutil.rmtree(_NOTES, ignore_errors=True)
    leaked = end_processes(_BASE_MARKER, grace_s=5.0)
    if leaked:
        print(f"\n{len(leaked)} ray_tpu process(es) outlived the run "
              f"(killed now):\n  {note_strays(_BASE_MARKER, leaked)}",
              file=sys.stderr)
