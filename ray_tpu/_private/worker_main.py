"""Worker process entry point (reference:
python/ray/_private/workers/default_worker.py). The asyncio loop runs on the
main thread; task execution happens in executor threads, so user code inside
tasks can call the public API through the same threadsafe bridge the driver
uses."""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import sys


def main():
    from ray_tpu._private.proc_util import set_pdeathsig_from_env
    set_pdeathsig_from_env()
    parser = argparse.ArgumentParser()
    parser.add_argument("--node-address", required=True)
    parser.add_argument("--gcs-address", required=True)
    parser.add_argument("--store-path", required=True)
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--session-name", default="session")
    args = parser.parse_args()
    logging.basicConfig(
        level=os.environ.get("RAY_TPU_LOG_LEVEL", "WARNING"),
        format=f"[worker {os.getpid()}] %(levelname)s %(message)s")

    # One process per chip: a worker starts pinned to the CPU backend and
    # only a lease that carries chips lifts the pin (worker.py
    # _apply_accelerator_ids), so a CPU-only task or actor that touches
    # JAX can never take the chip from the lease that owns it. Nothing
    # here imports jax; the pin and the compile-cache directory travel
    # through the environment it reads at import.
    from ray_tpu._private.accelerators import all_accelerator_managers
    for mgr in all_accelerator_managers().values():
        mgr.hide_accelerators_from_current_process()
    from ray_tpu._private.compile_cache import configure_compile_cache
    configure_compile_cache()

    from ray_tpu._private import worker as worker_mod
    from ray_tpu._private.worker import CoreWorker, Worker

    core = CoreWorker(mode="worker", gcs_address=args.gcs_address,
                      node_address=args.node_address,
                      store_path=args.store_path, node_id=args.node_id)
    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    from ray_tpu.util import sanitizers
    loop.run_until_complete(core.start_async())
    if sanitizers.enabled():
        loop.call_soon(sanitizers.maybe_install)
    worker_mod.global_worker = Worker(core, owns_loop=False)

    # crash black box: continuous on-disk mirror of this worker's event
    # ring + metrics snapshots; clean shutdown seals it in stop_async
    from ray_tpu._private import blackbox
    from ray_tpu._private.config import cfg
    blackbox.configure(
        cfg.blackbox_dir or f"/tmp/raytpu/{args.session_name}/blackbox",
        f"worker-{core.worker_id[:12]}", node_id=args.node_id,
        worker_id=core.worker_id)

    import ray_tpu
    ray_tpu._set_connected_from_worker(core)

    try:
        loop.run_forever()
    except KeyboardInterrupt:
        pass
    sys.exit(0)


if __name__ == "__main__":
    main()
