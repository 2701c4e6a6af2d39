"""The last assertion of the rule in tests/conftest.py (what a test file
starts ends with the file): nothing this pytest process started is still
alive.

Round-4 audit: a green 250-test run left 131 ray_tpu daemons alive —
GCS servers and node managers from crashed fixtures, node managers
retrying a dead GCS forever, workers orphaned by SIGKILLed node
managers. Every daemon a test file spawns carries that file's marker in
RAY_TPU_TEST_SESSION and the file's finalizer ends it and charges the
file. This test looks at the markers of its OWN worker only, whenever
xdist happens to hand it out: other workers are still inside their
files, and their daemons are theirs. It kills nothing.
"""

import os

from ray_tpu._private.proc_util import find_session_processes
from tests.conftest import WORKER_MARKER, describe_process, note_strays


def test_no_daemons_survive_the_suite():
    assert os.environ.get("RAY_TPU_TEST_SESSION", "").startswith(
        WORKER_MARKER), "conftest did not give this file a marker"
    strays = [f"pid {p}: {describe_process(p)}"
              for p in find_session_processes(WORKER_MARKER)]
    assert not strays, (
        f"{len(strays)} ray_tpu process(es) outlived their file's "
        f"finalizer on this worker:\n  "
        + note_strays(WORKER_MARKER, strays))
