"""Cluster weight-source resolution for replica construction.

ROADMAP item 3 leftover: a fleet shell revival used to RE-RUN the
deployment's ``params_fn`` — a full checkpoint read (or re-init) inside
every cold start, on every node, every time a scaled-to-zero deployment
woke up. The weight-distribution plane (PR 11) already solves exactly
this: one loaded tree broadcast once lands in every node's pinned arena,
and every later attach is a zero-copy local get.

``resolve_weight_source(key, loader)`` is the default path LLMDeployment
routes ``params_fn`` through (``fleet_weights_from_arena`` flag):

1. the GCS KV (namespace ``serve_weights``) is probed for a recorded
   broadcast ref under ``key`` — hit → ``ray_tpu.get`` attaches the tree
   from the local arena (cross-node pulls ride the zero-copy data
   plane); a stale/lost ref falls through;
2. miss → ``loader()`` runs ONCE (the only attach that pays the load),
   the host tree is published via ``ray_tpu.broadcast_weights`` — or a
   plain ``ray_tpu.put`` when the weight plane is unavailable (single
   node, no data plane) — and the ref is recorded for every future
   attach, shell revivals included.

``checkpoint_weight_source(path)`` builds a params_fn whose miss path is
``sharded_checkpoint.restore_and_broadcast`` — one host reads storage,
the fleet attaches from local arenas.

Outside a cluster everything degrades to a bare ``loader()`` call, so
the same deployment code runs in unit tests and bare scripts.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Optional

logger = logging.getLogger(__name__)

KV_NS = "serve_weights"


def _worker():
    from ray_tpu import _get_worker
    return _get_worker()


def _connected() -> bool:
    try:
        import ray_tpu
        return ray_tpu.is_initialized()
    except Exception:
        return False


def _host_tree(params: Any) -> Any:
    """Pull a params tree to host (numpy) leaves — the broadcastable
    form; device placement happens per-attach anyway."""
    import jax
    import numpy as np
    return jax.tree.map(lambda a: np.asarray(a), params)


def cached_ref(key: str):
    """The recorded broadcast ref for ``key``, or None."""
    import cloudpickle
    try:
        blob = _worker().gcs_call("kv_get", ns=KV_NS, key=key.encode())
    except Exception:
        return None
    if not blob:
        return None
    try:
        return cloudpickle.loads(blob)
    except Exception:
        return None


def record_ref(key: str, ref) -> None:
    import cloudpickle
    _worker().gcs_call("kv_put", ns=KV_NS, key=key.encode(),
                       value=cloudpickle.dumps(ref))


def clear_ref(key: str) -> None:
    try:
        _worker().gcs_call("kv_del", ns=KV_NS, key=key.encode())
    except Exception:
        logger.debug("weight-source kv_del failed for %s", key,
                     exc_info=True)


def publish_weights(key: str, params: Any):
    """Broadcast a loaded tree cluster-wide (plain-put fallback when the
    weight plane is unavailable) and record the ref under ``key``.
    Returns the ref, or None when even the put failed — callers always
    still hold the in-memory tree, so publish failures only cost the
    NEXT attach a reload."""
    import ray_tpu
    host = _host_tree(params)
    try:
        ref = ray_tpu.broadcast_weights(host)
    except Exception:
        try:
            ref = ray_tpu.put(host)
        except Exception:
            logger.warning("weight publish failed for %s", key,
                           exc_info=True)
            return None
    try:
        record_ref(key, ref)
    except Exception:
        logger.warning("weight-source ref record failed for %s", key,
                       exc_info=True)
        return None
    return ref


def resolve_weight_source(key: Optional[str], loader: Callable[[], Any],
                          *, enabled: Optional[bool] = None,
                          timeout_s: Optional[float] = None) -> Any:
    """Resolve a deployment's params through the cluster weight plane
    (see module docstring). Any failure along the arena path falls back
    to ``loader()`` — serving never breaks on weight-plane trouble. The
    whole of it is the launch phase ``launch.weights``, which says where
    the tree came from (``source`` = ``arena`` / ``loader``) and whether
    it was published."""
    from ray_tpu._private import events
    from ray_tpu._private.config import cfg
    if enabled is None:
        enabled = cfg.fleet_weights_from_arena
    with events.launch_phase("weights", key=key, source="loader",
                             published=False) as phase:
        if not enabled or not key or not _connected():
            return loader()
        ref = cached_ref(key)
        if ref is not None:
            try:
                import ray_tpu
                params = ray_tpu.get(
                    ref, timeout=(timeout_s if timeout_s is not None
                                  else cfg.fleet_attach_timeout_s))
                phase.set(source="arena")
                return params
            except Exception:
                # ref outlived its object (node loss, store restart):
                # forget it and reload below
                logger.info("weight-source ref for %s unreadable; "
                            "reloading", key, exc_info=True)
                clear_ref(key)
        params = loader()
        phase.set(published=publish_weights(key, params) is not None)
        return params


def checkpoint_weight_source(path: str,
                             key: Optional[str] = None
                             ) -> Callable[[], Any]:
    """A ``params_fn`` whose cold path is
    ``sharded_checkpoint.restore_and_broadcast``: the first attach reads
    the checkpoint off storage ONCE and fans it out over the weight
    plane; every other attach (and every shell revival) gets a local
    arena attach. Outside a cluster it reads the checkpoint directly."""
    key = key or f"ckpt/{path}"

    def params_fn():
        from ray_tpu.train.sharded_checkpoint import restore_host_arrays
        if not _connected():
            return restore_host_arrays(path)

        def loader():
            return restore_host_arrays(path)
        return resolve_weight_source(key, loader)
    return params_fn
