"""IMPALA / DQN / replay-buffer / V-trace tests (reference: rllib's
vtrace tests and tuned-example regressions; V-trace is checked against a
plain-python recursion, algorithms against CartPole smoke training)."""

import numpy as np

import ray_tpu
from ray_tpu.rl import AlgorithmConfig, ReplayBuffer


def test_vtrace_matches_python_recursion():
    import jax.numpy as jnp

    from ray_tpu.rl.vtrace import vtrace
    rng = np.random.default_rng(0)
    T, B = 7, 3
    b_logp = rng.normal(size=(T, B)).astype(np.float32) * 0.3
    t_logp = b_logp + rng.normal(size=(T, B)).astype(np.float32) * 0.2
    rewards = rng.normal(size=(T, B)).astype(np.float32)
    discounts = (0.9 * (rng.random((T, B)) > 0.2)).astype(np.float32)
    values = rng.normal(size=(T, B)).astype(np.float32)
    boot = rng.normal(size=(B,)).astype(np.float32)

    out = vtrace(jnp.asarray(b_logp), jnp.asarray(t_logp),
                 jnp.asarray(rewards), jnp.asarray(discounts),
                 jnp.asarray(values), jnp.asarray(boot))

    # plain-python reference recursion (IMPALA paper eq. 1)
    rhos = np.minimum(1.0, np.exp(t_logp - b_logp))
    cs = np.minimum(1.0, np.exp(t_logp - b_logp))
    vs = np.zeros((T, B), np.float32)
    acc = np.zeros(B, np.float32)
    for t in reversed(range(T)):
        v_tp1 = values[t + 1] if t + 1 < T else boot
        delta = rhos[t] * (rewards[t] + discounts[t] * v_tp1 - values[t])
        acc = delta + discounts[t] * cs[t] * acc
        vs[t] = acc + values[t]
    pg_adv = np.zeros((T, B), np.float32)
    for t in range(T):
        vs_tp1 = vs[t + 1] if t + 1 < T else boot
        pg_adv[t] = rhos[t] * (rewards[t] + discounts[t] * vs_tp1
                               - values[t])
    np.testing.assert_allclose(np.asarray(out.vs), vs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.pg_advantages), pg_adv,
                               rtol=1e-5, atol=1e-5)


def test_replay_buffer_ring_and_sampling():
    buf = ReplayBuffer(capacity=10)
    buf.add({"x": np.arange(6, dtype=np.float32)})
    assert len(buf) == 6
    buf.add({"x": np.arange(6, 14, dtype=np.float32)})   # wraps
    assert len(buf) == 10
    sample = buf.sample(32)["x"]
    # oldest entries (0..3) were overwritten by the wrap
    assert sample.min() >= 4.0
    assert set(np.unique(sample)).issubset(set(range(4, 14)))


def test_impala_cartpole_smoke(ray_start):
    from ray_tpu.rl import IMPALA
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=2,
                           rollout_fragment_length=32)
              .training(lr=5e-4))
    algo = IMPALA(config)
    try:
        for _ in range(3):
            out = algo.train()
        assert out["num_env_steps_sampled"] > 0
        assert np.isfinite(out["total_loss"])
        assert out["training_iteration"] == 3
    finally:
        algo.stop()


def test_dqn_cartpole_smoke(ray_start):
    from ray_tpu.rl import DQN
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=2,
                           rollout_fragment_length=32)
              .training(lr=1e-3, minibatch_size=64))
    algo = DQN(config)
    try:
        for _ in range(3):
            out = algo.train()
        assert out["replay_size"] > 0
        assert np.isfinite(out["td_loss"])
        assert out["epsilon"] < 1.0
    finally:
        algo.stop()
