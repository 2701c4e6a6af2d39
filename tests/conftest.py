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

import uuid  # noqa: E402

# Every daemon spawned during this pytest session inherits this marker in
# its environment; the suite-final hygiene check (test_zz_process_hygiene)
# scans /proc for survivors carrying it and fails the run if any daemon
# outlived its test (round-4 audit: 131 leaked processes after a green
# suite).
os.environ.setdefault("RAY_TPU_TEST_SESSION", uuid.uuid4().hex)

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
