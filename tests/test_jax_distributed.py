"""The real multi-host substrate, exercised end-to-end on CPU: two worker
processes form a jax.distributed world through the GCS-KV rendezvous and
run XLA collectives across process boundaries (reference:
python/ray/util/collective/collective.py NCCL group init + master
rendezvous; python/ray/train/_internal/backend_executor.py:68,135).

These are the CI stand-ins for multi-host TPU: same code path, CPU
devices (1 per process, Gloo-backed XLA collectives).
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig


@ray_tpu.remote(max_concurrency=1, num_cpus=1)
class XlaRank:
    """One process of an xla collective group (CPU backend)."""

    def __init__(self, world_size, rank, group):
        import jax
        jax.config.update("jax_platforms", "cpu")
        from ray_tpu.util import collective
        collective.init_collective_group(world_size, rank, backend="xla",
                                         group_name=group)
        self.rank = rank

    def allreduce_named(self, value, group, op="sum"):
        from ray_tpu.util import collective
        return np.asarray(collective.allreduce(np.asarray(value),
                                               group, op=op))

    def broadcast(self, value, src, group):
        from ray_tpu.util import collective
        return np.asarray(collective.broadcast(np.asarray(value), src,
                                               group))

    def allgather(self, value, group):
        from ray_tpu.util import collective
        return [np.asarray(x) for x in collective.allgather(
            np.asarray(value), group)]

    def world(self):
        import jax
        return [jax.process_count(), jax.local_device_count(),
                len(jax.devices())]


def test_xla_collective_group_two_processes():
    ray_tpu.init(num_cpus=4)
    try:
        group = "xg1"
        actors = [XlaRank.remote(2, r, group) for r in range(2)]
        # the device world spans both processes (each contributes its
        # local CPU devices — 8 under the test XLA_FLAGS)
        worlds = ray_tpu.get([a.world.remote() for a in actors],
                             timeout=180)
        for n_proc, n_local, n_total in worlds:
            assert n_proc == 2 and n_total == 2 * n_local
        # device-native psum across processes (ints stay exact)
        outs = ray_tpu.get(
            [a.allreduce_named.remote(np.array([r + 1, 10], np.int32),
                                      group)
             for r, a in enumerate(actors)], timeout=180)
        for o in outs:
            assert o.tolist() == [3, 20] and o.dtype == np.int32
        # broadcast from rank 1
        outs = ray_tpu.get(
            [a.broadcast.remote(
                np.full(3, 7.0) if r == 1 else np.zeros(3), 1, group)
             for r, a in enumerate(actors)], timeout=180)
        for o in outs:
            assert o.tolist() == [7.0, 7.0, 7.0]
        # allgather returns one entry per process
        outs = ray_tpu.get(
            [a.allgather.remote(np.array([float(r)]), group)
             for r, a in enumerate(actors)], timeout=180)
        for o in outs:
            assert len(o) == 2
            assert sorted(float(x[0]) for x in o) == [0.0, 1.0]
    finally:
        ray_tpu.shutdown()


def _dp_train_fn(config):
    """Data-parallel step over a 2-process global mesh: grads sync via
    sharding-driven psum, each process feeding its own batch shard."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    from ray_tpu import train as rt_train

    devs = np.array(jax.devices())
    assert jax.process_count() == 2, \
        f"expected 2-process world, got {jax.process_count()}"
    mesh = Mesh(devs, ("data",))
    rank = jax.process_index()
    n_total = len(devs)
    n_local = jax.local_device_count()

    w = jnp.zeros((4,))
    # one row per device; this process's rows carry (rank+1)
    local_x = np.full((n_local, 4), float(rank + 1), np.float32)

    def per_shard(w, x):
        # per-shard grad of mean((x@w - 1)^2), psum-averaged over data
        def loss(w):
            pred = x @ w
            return jnp.mean((pred - 1.0) ** 2)
        g = jax.grad(loss)(w)
        return jax.lax.pmean(g, "data")

    f = jax.jit(shard_map(per_shard, mesh=mesh, in_specs=(P(), P("data")),
                          out_specs=P(), check_vma=False))
    # global batch assembled from process-local shards; under
    # multi-process jit each process supplies only its local rows
    sharding = NamedSharding(mesh, P("data"))
    gx = jax.make_array_from_process_local_data(sharding, local_x,
                                                (n_total, 4))
    g = f(w, gx)
    # analytic: grad of mean((c*0 - 1)^2) wrt w at w=0 is -2*mean(x) per dim
    # (x columns are constant c per process: c=1 and c=2, pmean -> -3.0)
    expected = -2.0 * (1.0 + 2.0) / 2.0
    got = np.asarray(jax.device_get(g))
    assert np.allclose(got, expected, atol=1e-5), (got, expected)
    rt_train.report({"grad0": float(got[0]), "rank": rank})


def test_jax_trainer_two_process_world():
    ray_tpu.init(num_cpus=4)
    try:
        trainer = JaxTrainer(
            _dp_train_fn,
            scaling_config=ScalingConfig(num_workers=2,
                                         use_jax_distributed=True),
            run_config=RunConfig(name="jd-e2e"),
        )
        result = trainer.fit()
        assert result.error is None, result.error
        assert result.metrics.get("grad0") == pytest.approx(-3.0)
    finally:
        ray_tpu.shutdown()
