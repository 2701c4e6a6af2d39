"""Stopping the runtime and seeing that nothing of it is left: no process
holds the chip, none of the runtime's processes outlives the run (from
chip_smoke.py's _wait_chip_released and _wait_runtime_gone). And the
clock thread by which a run sees that its machine stood still."""

from __future__ import annotations

import os
import signal
import sys
import time

SESSION_ENV = "RAY_TPU_TEST_SESSION"     # proc_util's hygiene marker


def watch_clock(stop, gaps, tick_s=0.01, report_s=0.1):
    """A thread that sleeps `tick_s` at a time and notes every wake-up that
    came `report_s` late or more, as (when it went to sleep, how long it
    was gone): how the run sees that its process, or the whole host, stood
    still. PR 21 saw the v5e host freeze for seconds when a TPU runtime
    opens or closes anywhere on it."""
    last = time.monotonic()
    while not stop.wait(tick_s):
        now = time.monotonic()
        if now - last >= report_s:
            gaps.append((last, now - last))
        last = now


def shutdown_and_verify(checks, serve: bool) -> None:
    import ray_tpu
    from ray_tpu._private.accelerators.tpu import processes_holding_chips
    from ray_tpu._private.proc_util import find_session_processes
    try:
        if serve:
            from ray_tpu import serve as serve_api
            serve_api.shutdown()
    finally:
        ray_tpu.shutdown()
    deadline = time.monotonic() + 60
    holders = processes_holding_chips()
    while holders and time.monotonic() < deadline:
        time.sleep(0.5)
        holders = processes_holding_chips()
    checks.check(not holders, f"processes {holders} still hold the chip "
                              f"after the runtime shut down")
    marker = os.environ[SESSION_ENV]
    deadline = time.monotonic() + 30
    left = list(find_session_processes(marker))
    while left and time.monotonic() < deadline:
        time.sleep(0.5)
        left = list(find_session_processes(marker))
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    checks.check(not left, f"runtime processes {left} outlived the "
                           f"shutdown and were killed")
    xb = sys.modules.get("jax._src.xla_bridge")
    checks.check(not (xb and xb.backends_are_initialized()),
                 "the load-generating process initialised a JAX backend")
