"""Worker-group orchestration for distributed training (reference:
python/ray/train/_internal/backend_executor.py:68 BackendExecutor +
_internal/worker_group.py:102 WorkerGroup).

A training run = a placement group (gang) + one actor per worker +
rank/world wiring + a backend hook that initializes jax.distributed
(coordinator rendezvous through GCS KV — the NCCL/TCP-store replacement).
Worker failures surface as ActorDiedError on the run refs. Restart
granularity follows ``FailureConfig.restart_policy``: under "job" the
trainer restarts the whole gang from the latest checkpoint; under
"stage" the executor replaces ONLY the dead workers in place
(:meth:`BackendExecutor.replace_failed_workers` — same bundle, same
rank, latest-checkpoint resume pushed to the fresh actor) while the
survivors keep running. Per-worker replace is refused (job restart
instead) when the gang runs jax.distributed collectives or a slice
topology — those fail as a unit."""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.session import TrainContext, _init_session
from ray_tpu.util import (PlacementGroupSchedulingStrategy, placement_group,
                          remove_placement_group)


class TrainWorker:
    """Actor hosting one training worker (needs max_concurrency=2 so
    poll()/get_address() answer while run() blocks)."""

    def __init__(self):
        self._session = None
        self._context = None

    def setup(self, world_size: int, rank: int, local_rank: int,
              node_rank: int):
        self._context = TrainContext(world_size=world_size, world_rank=rank,
                                     local_rank=local_rank,
                                     node_rank=node_rank)
        self._session = _init_session(self._context)
        return True

    def set_resume_checkpoint(self, ckpt):
        if self._session is not None:
            self._session.latest_checkpoint = ckpt
        return True

    def set_dataset_shards(self, shards):
        if self._session is not None:
            self._session.dataset_shards = shards
        return True

    def get_node_ip(self):
        from ray_tpu._private.rpc import node_ip_address
        return node_ip_address()

    def get_node_id(self):
        from ray_tpu._private.worker import global_worker
        return global_worker.core.node_id

    def setup_jax_distributed(self, group_name: str, world_size: int,
                              rank: int):
        # rank 0 binds a free port on ITS host and publishes via GCS KV
        # (the collective rendezvous helper), so no port guessing
        from ray_tpu.util.collective import _init_jax_distributed
        _init_jax_distributed(world_size, rank, group_name)
        return True

    def run(self, fn, config):
        import inspect
        # a loop that came with JAX imported is watched from its first
        # compile (make_train_fns asks again, for one that imports it
        # only now): `xla.*` spans under this task's trace
        from ray_tpu._private import compile_cache
        compile_cache.watch()
        try:
            takes_arg = len(inspect.signature(fn).parameters) >= 1
        except (TypeError, ValueError):
            takes_arg = config is not None
        if takes_arg:
            fn(config if config is not None else {})
        else:
            fn()
        return True

    def poll(self):
        if self._session is None:
            return []
        return self._session.drain()

    def ping(self):
        return True


def acquire_slice_bundles(topology: str,
                          worker_resources: Dict[str, float],
                          num_workers: Optional[int] = None,
                          wait_timeout_s: Optional[float] = None):
    """Wait for a whole healthy multi-host slice and return
    ``(pod, bundles, "STRICT_SPREAD")`` — the slice-gang acquisition
    shared by :meth:`BackendExecutor.start` and the MPMD stage gangs
    (``train.mpmd.GangStageHandle``), where one pipeline stage is a gang
    of workers over one multi-host mesh. Competing gangs / restarting
    nodes make slice availability transient; staying in the wait keeps
    the demand visible instead of burning the caller's failure budget
    instantly. Returns ``(None, None, None)`` for single-host
    topologies (no gang needed)."""
    from ray_tpu.train import slice as slice_lib
    n_hosts, chips = slice_lib.slice_shape(topology)
    if n_hosts <= 1:
        return None, None, None
    if num_workers is not None and num_workers != n_hosts:
        raise ValueError(
            f"topology {topology} has {n_hosts} hosts; "
            f"num_workers={num_workers} must match")
    from ray_tpu._private.config import cfg as _cfg
    deadline = time.monotonic() + (
        wait_timeout_s if wait_timeout_s is not None
        else _cfg.slice_wait_timeout_s)
    pod = None
    while pod is None:
        pod = slice_lib.pick_slice(ray_tpu.nodes(), topology)
        if pod is None:
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"no healthy {topology} slice available "
                    f"({n_hosts} hosts with {chips} free chips each)")
            time.sleep(1.0)
    bundles = slice_lib.slice_bundles(pod, topology, worker_resources)
    return pod, bundles, "STRICT_SPREAD"


class BackendExecutor:
    def __init__(self, scaling_config: ScalingConfig,
                 use_jax_distributed: bool = False):
        self.scaling = scaling_config
        self.use_jax_distributed = use_jax_distributed
        self.pg = None
        self.workers: List = []
        self.run_refs: List = []
        self.slice_pod = None
        self._bundles: List[Dict] = []
        self._dataset_shards = None
        self._run_fn = None
        self._run_config = None

    def start(self):
        n = self.scaling.num_workers
        res = self.scaling.worker_resources()
        strategy = self.scaling.placement_strategy
        bundles = [dict(res) for _ in range(n)]
        topology = self.scaling.topology
        if topology:
            # slice gang: one worker per slice host, pinned to ONE healthy
            # slice via its pod resource, STRICT_SPREAD across its hosts
            # (fails-as-a-unit semantics come from the trainer restarting
            # the whole gang on any worker/node death)
            try:
                pod, slice_bundles, slice_strategy = acquire_slice_bundles(
                    topology, res, num_workers=n)
            except ValueError as e:
                raise ValueError(str(e).replace(
                    "num_workers", "ScalingConfig.num_workers")) from None
            if pod is not None:
                bundles = slice_bundles
                strategy = slice_strategy
                self.slice_pod = pod
        self.pg = placement_group(bundles, strategy=strategy)
        if not self.pg.wait(timeout=60):
            remove_placement_group(self.pg)
            raise RuntimeError(
                f"placement group for {bundles} not schedulable")
        self._bundles = bundles
        self.workers = [self._spawn_worker(i) for i in range(n)]
        # ranks: worker order; local/node ranks by node ip grouping
        ips = ray_tpu.get([w.get_node_ip.remote() for w in self.workers],
                          timeout=120)
        node_order: Dict[str, int] = {}
        local_counters: Dict[str, int] = {}
        setups = []
        self._setup_args: List[tuple] = []
        for rank, (w, ip) in enumerate(zip(self.workers, ips)):
            node_rank = node_order.setdefault(ip, len(node_order))
            local_rank = local_counters.get(ip, 0)
            local_counters[ip] = local_rank + 1
            self._setup_args.append((n, rank, local_rank, node_rank))
            setups.append(w.setup.remote(n, rank, local_rank, node_rank))
        ray_tpu.get(setups, timeout=120)
        if self.use_jax_distributed:
            import uuid
            group = f"train-{uuid.uuid4().hex[:8]}"
            ray_tpu.get([w.setup_jax_distributed.remote(group, n, r)
                         for r, w in enumerate(self.workers)], timeout=300)

    def set_resume_checkpoint(self, ckpt):
        ray_tpu.get([w.set_resume_checkpoint.remote(ckpt)
                     for w in self.workers], timeout=60)

    def setup_datasets(self, datasets, data_config=None):
        """Streaming-split datasets across the worker gang (reference:
        DataConfig streaming split into Train, _internal/data_config.py:
        one executing stream per dataset, one disjoint shard per worker)."""
        from ray_tpu.data.split import streaming_split
        split_names = getattr(data_config, "datasets_to_split", "all") \
            if data_config is not None else "all"
        n = len(self.workers)
        # locality hints (fetched lazily, once, only if a split happens):
        # bundles already resident on a worker's node deal to that
        # worker (split.py locality-aware dealing)
        hints_box: List = []

        def _hints():
            if not hints_box:
                try:
                    hints_box.append(ray_tpu.get(
                        [w.get_node_id.remote() for w in self.workers],
                        timeout=60))
                except Exception:
                    hints_box.append(None)
            return hints_box[0]

        per_worker = {i: {} for i in range(n)}
        for name, ds in datasets.items():
            split = split_names == "all" or name in split_names
            if split and n > 1:
                shards = streaming_split(ds, n, locality_hints=_hints())
                for i in range(n):
                    per_worker[i][name] = shards[i]
            else:
                # replicated: each worker gets its own full stream
                for i in range(n):
                    per_worker[i][name] = streaming_split(ds, 1)[0]
        # the ORIGINAL coordinator handles live in these iterators: they
        # must outlive the run (worker-side copies are non-owning, and
        # dropping the originals would kill the coordinators mid-stream)
        self._dataset_shards = per_worker
        ray_tpu.get([w.set_dataset_shards.remote(per_worker[i])
                     for i, w in enumerate(self.workers)], timeout=120)

    def _spawn_worker(self, bundle_index: int):
        actor_cls = ray_tpu.remote(TrainWorker)
        return actor_cls.options(
            max_concurrency=2,
            resources=dict(self._bundles[bundle_index]),  # consumes bundle
            scheduling_strategy=PlacementGroupSchedulingStrategy(
                self.pg, placement_group_bundle_index=bundle_index),
        ).remote()

    def start_training(self, fn: Callable, config):
        self._run_fn, self._run_config = fn, config
        self.run_refs = [w.run.remote(fn, config) for w in self.workers]
        return self.run_refs

    def poll_results(self) -> List[List[Dict]]:
        """Drain buffered report() rows per worker. A dead worker
        contributes an empty list instead of failing the sweep — its
        death is surfaced by finished() / failed_worker_indexes(), and
        under restart_policy="stage" the survivors' metrics must keep
        flowing while the replacement builds."""
        out: List[List[Dict]] = []
        for w in self.workers:
            try:
                out.append(ray_tpu.get(w.poll.remote(), timeout=60))
            except Exception:
                out.append([])
        return out

    def finished(self):
        """(done, error): done when every run ref resolved; error holds the
        first worker failure."""
        ready, not_ready = ray_tpu.wait(self.run_refs,
                                        num_returns=len(self.run_refs),
                                        timeout=0)
        if not_ready:
            # check for failed ones among ready
            for r in ready:
                try:
                    ray_tpu.get(r, timeout=1)
                except Exception as e:
                    return True, e
            return False, None
        try:
            ray_tpu.get(self.run_refs, timeout=5)
            return True, None
        except Exception as e:
            return True, e

    # -------------------------------------------------- per-worker replace
    def supports_worker_replace(self) -> bool:
        """Per-worker replace is sound only when workers are independent
        processes: a jax.distributed gang's collectives hang on a member
        swap (the group rendezvous is immutable) and a slice topology
        fails as a unit — both degrade to the job-level restart."""
        return not self.use_jax_distributed and self.slice_pod is None

    def failed_worker_indexes(self) -> List[int]:
        """Workers whose run ref resolved with an error (actor death or
        a raised training loop); survivors' refs stay pending."""
        failed = []
        for i, ref in enumerate(self.run_refs):
            ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=0)
            if not ready:
                continue
            try:
                ray_tpu.get(ref, timeout=1)
            except Exception:
                failed.append(i)
        return failed

    def replace_failed_workers(self, resume_checkpoint=None) -> List[int]:
        """Build a fresh actor in each dead worker's bundle, re-wire its
        rank, push the latest checkpoint + its dataset shards, and
        restart its training loop — the surviving workers never stop.
        Returns the replaced indexes (empty when nothing was dead or
        replace is unsupported)."""
        if not self.supports_worker_replace():
            return []
        failed = self.failed_worker_indexes()
        if not failed:
            return []
        from ray_tpu._private import events
        for i in failed:
            try:
                ray_tpu.kill(self.workers[i])
            except Exception:
                pass   # already dead
            w = self._spawn_worker(i)
            ray_tpu.get(w.setup.remote(*self._setup_args[i]), timeout=120)
            if resume_checkpoint is not None:
                ray_tpu.get(w.set_resume_checkpoint.remote(
                    resume_checkpoint), timeout=60)
            if self._dataset_shards is not None:
                ray_tpu.get(w.set_dataset_shards.remote(
                    self._dataset_shards[i]), timeout=120)
            self.workers[i] = w
            self.run_refs[i] = w.run.remote(self._run_fn, self._run_config)
            events.record_instant(
                "train.worker_replaced", category="train", rank=i,
                resumed=bool(resume_checkpoint is not None))
        return failed

    def shutdown(self):
        self._dataset_shards = None
        self.run_refs = []
        # gang teardown: surviving workers of a partially-failed slice
        # must die with it (a half-dead slice can't run collectives and
        # its actors would leak leases + chips otherwise)
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
            self.pg = None
