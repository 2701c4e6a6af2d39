"""Collective communication between actors/tasks.

API parity with the reference's ray.util.collective (reference:
python/ray/util/collective/collective.py:40-655 — init_collective_group,
allreduce/allgather/reducescatter/broadcast/barrier/send/recv), with the
backends re-based for TPU:

- "xla": device-tensor collectives. Rendezvous through GCS KV (replaces the
  NCCL TCP store), then `jax.distributed.initialize`; the actual collectives
  are XLA ICI/DCN ops inside jit (psum/all_gather) over the processes'
  global devices — NCCL/cupy is replaced entirely.
- "store": host-array collectives through the object store + GCS KV
  (replaces pygloo). Works anywhere, used for small host payloads and in
  CPU-only tests.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu._private.config import cfg

_GROUPS: Dict[str, "CollectiveGroup"] = {}


class CollectiveGroup:
    def __init__(self, world_size: int, rank: int, backend: str,
                 group_name: str):
        self.world_size = world_size
        self.rank = rank
        self.backend = backend
        self.group_name = group_name
        self._seq = 0


def _kv():
    from ray_tpu import _get_worker
    return _get_worker()


def _kv_put(key: str, value: bytes):
    _kv().gcs_call("kv_put", ns="collective", key=key.encode(), value=value)


def _kv_get(key: str, timeout: float = 60.0) -> bytes:
    deadline = time.monotonic() + timeout
    while True:
        v = _kv().gcs_call("kv_get", ns="collective", key=key.encode())
        if v is not None:
            return v
        if time.monotonic() > deadline:
            raise TimeoutError(f"collective rendezvous timed out on {key}")
        time.sleep(cfg.wait_poll_floor_s)


def init_collective_group(world_size: int, rank: int,
                          backend: str = "store",
                          group_name: str = "default") -> CollectiveGroup:
    if backend == "xla":
        _init_jax_distributed(world_size, rank, group_name)
    group = CollectiveGroup(world_size, rank, backend, group_name)
    _GROUPS[group_name] = group
    return group


def _init_jax_distributed(world_size: int, rank: int, group_name: str):
    """jax.distributed.initialize with GCS-KV coordinator rendezvous
    (our KV replaces NCCL's TCP store; reference rendezvous:
    util/collective master address through named actors)."""
    import jax

    key = f"{group_name}:coordinator"
    if rank == 0:
        import socket
        from ray_tpu._private.rpc import node_ip_address
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        addr = f"{node_ip_address()}:{port}"
        _kv_put(key, addr.encode())
    else:
        addr = _kv_get(key).decode()
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=world_size,
                               process_id=rank)


def destroy_collective_group(group_name: str = "default"):
    _GROUPS.pop(group_name, None)


def get_rank(group_name: str = "default") -> int:
    return _GROUPS[group_name].rank


def get_collective_group_size(group_name: str = "default") -> int:
    return _GROUPS[group_name].world_size


def _store_exchange(group: CollectiveGroup, payload: np.ndarray,
                    tag: str) -> List[np.ndarray]:
    """All ranks publish, all ranks read all (store backend primitive).
    The trailing ack round keeps every rank's ObjectRef alive until all
    ranks have fetched it (otherwise the owner GCs the object under a
    slower reader)."""
    import cloudpickle as cp
    import ray_tpu
    seq = group._seq
    group._seq += 1
    key = f"{group.group_name}:{tag}:{seq}"
    ref = ray_tpu.put(payload)
    _kv_put(f"{key}:{group.rank}", cp.dumps(ref))
    outs: List[Optional[np.ndarray]] = []
    for r in range(group.world_size):
        if r == group.rank:
            outs.append(payload)
            continue
        blob = _kv_get(f"{key}:{r}")
        outs.append(ray_tpu.get(cp.loads(blob)))
    _kv_put(f"{key}:ack:{group.rank}", b"1")
    for r in range(group.world_size):
        _kv_get(f"{key}:ack:{r}")
    del ref
    return outs


_REDUCERS = {"sum": np.add, "product": np.multiply,
             "min": np.minimum, "max": np.maximum}


def allreduce(tensor, group_name: str = "default", op: str = "sum"):
    group = _GROUPS[group_name]
    if group.backend == "xla":
        return _xla_allreduce(tensor, op)
    arr = np.asarray(tensor)
    parts = _store_exchange(group, arr, "ar")
    reducer = _REDUCERS[op]
    out = parts[0].copy()
    for p in parts[1:]:
        out = reducer(out, p)
    return out


# jit cache for the device-collective closures: jax.jit keys on function
# identity, so a fresh shard_map per call would retrace + recompile every
# invocation. Keyed by (kind, op/src, ndev) — shapes/dtypes are handled by
# jit's own cache once the callable is stable.
_XLA_FNS: Dict[tuple, Any] = {}


def _xla_mesh():
    import jax
    from jax.sharding import Mesh

    devs = np.array(jax.devices())
    return Mesh(devs, ("all",)), jax.local_device_count()


def _xla_allreduce(tensor, op: str):
    """Cross-process device allreduce: under jax.distributed all processes'
    devices form one global mesh; psum over it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if op not in ("sum", "max", "min", "product"):
        raise ValueError(f"unsupported allreduce op {op!r}")
    mesh, n_local = _xla_mesh()
    key = ("ar", op, mesh.size)
    fn = _XLA_FNS.get(key)
    if fn is None:
        def f(x):
            import jax.lax as lax
            if op == "product":
                # pprod via psum of logs is lossy — use all_gather+reduce;
                # P() replicates per process onto its local devices: take
                # one representative per process (homogeneous hosts)
                g = lax.all_gather(x, "all")
                return jnp.prod(g[::n_local], axis=0)
            out = getattr(lax, {"sum": "psum", "max": "pmax",
                                "min": "pmin"}[op])(x, "all")
            if op == "sum":
                # P() replicates each process's tensor onto all of its
                # local devices; psum then counts every local copy —
                # divide the multiplicity back out. Integer dtypes use
                # integer floordiv (exact: value is k*n_local) so large
                # sums never round through float32.
                if jnp.issubdtype(x.dtype, jnp.integer):
                    out = out // n_local
                else:
                    out = (out / n_local).astype(x.dtype)
            return out

        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False))
        _XLA_FNS[key] = fn
    return fn(tensor)


def allgather(tensor, group_name: str = "default") -> List[np.ndarray]:
    group = _GROUPS[group_name]
    if group.backend == "xla":
        return _xla_allgather(tensor)
    arr = np.asarray(tensor)
    return _store_exchange(group, arr, "ag")


def _xla_allgather(tensor) -> List:
    """Device all_gather across all processes' devices; returns one entry
    per process (mirrors the store backend's per-rank list)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh, n_local = _xla_mesh()
    key = ("ag", mesh.size)
    fn = _XLA_FNS.get(key)
    if fn is None:
        def f(x):
            # every shard computes the identical [n_dev, ...] stack, so the
            # result is replicated — out_specs=P() returns it once
            return jax.lax.all_gather(x, "all")

        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False))
        _XLA_FNS[key] = fn
    out = fn(tensor)
    # one representative copy per process (each process's tensor was
    # replicated over its local devices)
    return [out[i] for i in range(0, out.shape[0], n_local)]


def reducescatter(tensor, group_name: str = "default", op: str = "sum"):
    group = _GROUPS[group_name]
    out = allreduce(tensor, group_name, op)
    chunks = np.array_split(out, group.world_size)
    return chunks[group.rank]


def broadcast(tensor, src_rank: int = 0, group_name: str = "default"):
    group = _GROUPS[group_name]
    if group.backend == "xla":
        return _xla_broadcast(tensor, src_rank, group)
    import ray_tpu
    import cloudpickle as cp
    seq = group._seq
    group._seq += 1
    key = f"{group.group_name}:bc:{seq}"
    if group.rank == src_rank:
        ref = ray_tpu.put(np.asarray(tensor))
        _kv_put(key, cp.dumps(ref))
        # hold the ref until every rank has fetched
        for r in range(group.world_size):
            if r != src_rank:
                _kv_get(f"{key}:ack:{r}")
        del ref
        return np.asarray(tensor)
    out = ray_tpu.get(cp.loads(_kv_get(key)))
    _kv_put(f"{key}:ack:{group.rank}", b"1")
    return out


def barrier(group_name: str = "default"):
    group = _GROUPS[group_name]
    seq = group._seq
    group._seq += 1
    _kv_put(f"{group.group_name}:bar:{seq}:{group.rank}", b"1")
    for r in range(group.world_size):
        _kv_get(f"{group.group_name}:bar:{seq}:{r}")


def _xla_broadcast(tensor, src_rank: int, group: CollectiveGroup):
    """Device broadcast as a psum where non-source processes contribute
    zeros (every process passes a same-shaped buffer, like the reference
    API). Stays entirely on-device over ICI/DCN."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh, n_local = _xla_mesh()
    contrib = (jnp.asarray(tensor) if group.rank == src_rank
               else jnp.zeros_like(jnp.asarray(tensor)))
    key = ("bc", mesh.size)
    fn = _XLA_FNS.get(key)
    if fn is None:
        def f(x):
            # divide the per-process local-device multiplicity back out;
            # integer floordiv keeps large integer payloads exact
            s = jax.lax.psum(x, "all")
            if jnp.issubdtype(x.dtype, jnp.integer):
                return s // n_local
            return (s / n_local).astype(x.dtype)

        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False))
        _XLA_FNS[key] = fn
    return fn(contrib)


# NOTE: send/recv are host-mediated (object store + GCS KV) on every
# backend: XLA has no true point-to-point primitive outside compiled
# collectives (ppermute needs all devices in the program); device-to-device
# P2P belongs to compiled-DAG channels (experimental/channel.py), not this
# eager API.
_P2P_SEQ: Dict[tuple, int] = {}


def send(tensor, dst_rank: int, group_name: str = "default"):
    group = _GROUPS[group_name]
    import ray_tpu
    import cloudpickle as cp
    key = (group_name, group.rank, dst_rank)
    seq = _P2P_SEQ.get(key, 0)
    _P2P_SEQ[key] = seq + 1
    ref = ray_tpu.put(np.asarray(tensor))
    tag = f"{group.group_name}:p2p:{seq}:{group.rank}:{dst_rank}"
    _kv_put(tag, cp.dumps(ref))
    _kv_get(f"{tag}:ack")       # hold ref until the receiver has fetched
    del ref


def recv(src_rank: int, group_name: str = "default"):
    group = _GROUPS[group_name]
    import ray_tpu
    import cloudpickle as cp
    key = (group_name, src_rank, group.rank)
    seq = _P2P_SEQ.get(key, 0)
    _P2P_SEQ[key] = seq + 1
    tag = f"{group.group_name}:p2p:{seq}:{src_rank}:{group.rank}"
    blob = _kv_get(tag)
    out = ray_tpu.get(cp.loads(blob))
    _kv_put(f"{tag}:ack", b"1")
    return out
