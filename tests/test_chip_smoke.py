"""chip_smoke.py's control flow rehearsed on the CPU, and the runtime rule
it leans on: one process per chip."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env=None, timeout=300):
    env = {**os.environ, "PYTHONPATH": REPO, **(env or {})}
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=timeout, cwd=REPO)


def test_chip_smoke_control_flow_fails_without_a_tpu():
    """Both phases run end to end at llama-debug size through main()'s
    test-only argument; every check holds except the ones that need the
    chip, so the exit code is not 0 and no ok line is printed."""
    p = _run("""
        import sys
        import chip_smoke
        sys.exit(chip_smoke.main([], _test_sizes={
            "model": "llama-debug", "n_slots": 2, "max_len": 256,
            "prefill_chunk": 32, "prefill_budget": 64,
            "prompt_lens": (4, 40, 200), "max_new_tokens": 8,
            "train_batch": 2, "train_len": 128, "attention_impl": "auto",
            "resources": {"TPU": 1}, "stop_at_first_failure": False}))
        """, env={"XLA_FLAGS": ""})     # one CPU device, like one chip
    assert p.returncode != 0, p.stdout
    assert '"ok": true' not in p.stdout
    phases = {}
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            row = json.loads(line)
            phases[row["phase"]] = row
    assert set(phases) == {"serve", "train"}, (p.stdout, p.stderr[-2000:])
    serve, train = phases["serve"], phases["train"]
    assert serve["device"]["platform"] == "cpu"
    assert serve["decode_compile_count"] == 1
    assert [r["tokens"] for r in serve["requests"]] == [8, 8, 8, 64]
    assert serve["requests"][-1]["via"] == "http"
    assert len(train["losses"]) == 5
    assert train["losses"][-1] < train["losses"][0]
    failed = [ln for ln in p.stderr.splitlines()
              if ln.startswith("CHECK FAILED")]
    # what failed is the device, nothing else: not the requests, not the
    # loss, not the parent's own abstinence from JAX
    assert len(failed) == 4, failed
    assert all("'tpu'" in ln or "tpu_custom_call" in ln or "TPU chip" in ln
               for ln in failed), failed


def test_chip_smoke_stops_at_once_when_the_node_has_no_chip():
    p = _run("import sys, chip_smoke; sys.exit(chip_smoke.main([]))",
             timeout=120)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "advertises TPU=0" in p.stderr


def test_lease_without_tpu_cannot_open_the_tpu_backend():
    """A worker starts pinned to the CPU; a lease that carries no chip
    keeps it there, whatever the node itself was started with."""
    p = _run("""
        import os
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"     # a TPU node's setting
        from ray_tpu._private.accelerators import all_accelerator_managers
        from ray_tpu._private.worker import CoreWorker
        for mgr in all_accelerator_managers().values():   # worker_main
            mgr.hide_accelerators_from_current_process()
        CoreWorker._apply_accelerator_ids(None, {"task_id": b"t"})
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert "TPU_VISIBLE_CHIPS" not in os.environ
        import jax
        assert jax.config.jax_platforms == "cpu"
        assert {d.platform for d in jax.devices()} == {"cpu"}
        # the CPU backend is open now: this process can never serve a
        # chip lease, and says so instead of running it on the CPU
        try:
            CoreWorker._apply_accelerator_ids(
                None, {"accelerator_ids": {"TPU": ["0"]}})
        except RuntimeError as e:
            assert "worker process of its own" in str(e)
        else:
            raise AssertionError("a used CPU worker took a chip lease")
        print("held off")
        """, env={"JAX_PLATFORMS": ""})
    assert p.returncode == 0 and "held off" in p.stdout, p.stderr[-2000:]


@pytest.mark.parametrize("jax_first", [False, True],
                         ids=["before-jax-import", "after-jax-import"])
def test_lease_with_one_tpu_sees_exactly_its_chip(jax_first):
    p = _run(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
        from ray_tpu._private.accelerators import all_accelerator_managers
        from ray_tpu._private.worker import CoreWorker
        for mgr in all_accelerator_managers().values():
            mgr.hide_accelerators_from_current_process()
        if {jax_first}:
            import jax
        CoreWorker._apply_accelerator_ids(
            None, {{"accelerator_ids": {{"TPU": ["2"]}}}})
        assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
        assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"
        import jax
        assert jax.config.jax_platforms == "tpu,cpu"
        print("granted")
        """, env={"JAX_PLATFORMS": ""})
    assert p.returncode == 0 and "granted" in p.stdout, p.stderr[-2000:]


def test_chip_lease_gets_its_own_worker_and_retires_it():
    """Through the runtime: a num_tpus task runs in a process that no
    CPU task ever ran in, sees its chip, and that process is gone — not
    pooled — once the lease is returned."""
    p = _run("""
        import os, time
        import ray_tpu
        ray_tpu.init(num_cpus=2, resources={"TPU": 1})

        @ray_tpu.remote
        def cpu_task():
            return os.getpid(), os.environ.get("JAX_PLATFORMS")

        @ray_tpu.remote(num_tpus=1)
        def tpu_task():
            return (os.getpid(), os.environ.get("JAX_PLATFORMS"),
                    os.environ.get("TPU_VISIBLE_CHIPS"))

        cpu_pid, cpu_plat = ray_tpu.get(cpu_task.remote())
        tpu_pid, tpu_plat, chips = ray_tpu.get(tpu_task.remote())
        assert cpu_plat == "cpu" and tpu_plat == "cpu,tpu-node", (
            cpu_plat, tpu_plat)
        assert chips == "0" and tpu_pid != cpu_pid
        deadline = time.time() + 30
        while time.time() < deadline and os.path.exists(f"/proc/{tpu_pid}"):
            time.sleep(0.2)
        assert not os.path.exists(f"/proc/{tpu_pid}"), "chip worker pooled"
        # the chip is leasable again, by another fresh process
        again = ray_tpu.get(tpu_task.remote())[0]
        assert again not in (tpu_pid, cpu_pid)
        ray_tpu.shutdown()
        print("retired")
        """, env={"JAX_PLATFORMS": "cpu,tpu-node"})
    assert p.returncode == 0 and "retired" in p.stdout, p.stderr[-3000:]
