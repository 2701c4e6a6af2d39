"""Tuned-examples-style regression gates: each config must hit a reward
threshold within a step budget (reference: rllib/tuned_examples/ppo/ +
rllib/tests/run_regression_tests.py — pass = stop-reward reached).

Covers the three module families: MLP/discrete (CartPole), Gaussian/
continuous (Pendulum), CNN/discrete (the built-in GridTarget pixel env).
"""

import numpy as np

import ray_tpu
from ray_tpu.rl import AlgorithmConfig


RAY_START = dict(num_cpus=4, object_store_memory=256 * 1024 * 1024)


def _run_until(config, stop_reward, max_iters, patience_improve=None):
    algo = config.build()
    best, first = -np.inf, None
    try:
        for i in range(max_iters):
            r = algo.train()["episode_return_mean"]
            if r is None:
                continue
            first = r if first is None else first
            best = max(best, r)
            if best >= stop_reward:
                break
    finally:
        algo.stop()
    return first, best


def test_ppo_cartpole_threshold(ray_start):
    """Discrete/MLP gate (reference: tuned_examples/ppo/cartpole_ppo.py,
    stop reward 150 on a small budget)."""
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                           rollout_fragment_length=64)
              .training(train_batch_size=512, minibatch_size=128,
                        num_epochs=6, lr=3e-4, entropy_coeff=0.01))
    first, best = _run_until(config, stop_reward=150, max_iters=40)
    assert best >= 150, (first, best)


def test_ppo_pendulum_continuous_threshold(ray_start):
    """Continuous/Gaussian gate (reference:
    tuned_examples/ppo/pendulum_ppo.py). Random policy averages ~-1250;
    an improving Gaussian PPO reaches -1000 quickly."""
    config = (AlgorithmConfig()
              .environment("Pendulum-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                           rollout_fragment_length=128)
              .training(train_batch_size=2048, minibatch_size=256,
                        num_epochs=10, lr=1e-3, entropy_coeff=0.0,
                        gamma=0.95, lambda_=0.95, clip_param=0.3,
                        vf_loss_coeff=0.5))
    first, best = _run_until(config, stop_reward=-1000, max_iters=45)
    assert best >= -1000, (first, best)


def test_ppo_pixel_env_conv_threshold(ray_start):
    """CNN/discrete gate on the built-in pixel env: random play averages
    about -0.5 per episode; a learned policy clears +0.2."""
    config = (AlgorithmConfig()
              .environment("ray_tpu/GridTarget-v0")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                           rollout_fragment_length=64)
              .training(train_batch_size=1024, minibatch_size=256,
                        num_epochs=6, lr=1e-3, entropy_coeff=0.01,
                        gamma=0.95))
    first, best = _run_until(config, stop_reward=0.2, max_iters=30)
    assert best >= 0.2, (first, best)


def test_sac_pendulum_threshold(ray_start):
    """SAC gate (reference: tuned_examples/sac/pendulum_sac.py) —
    off-policy continuous control; far more sample-efficient than PPO,
    so the budget is a handful of iterations."""
    from ray_tpu.rl.sac import SAC
    config = (AlgorithmConfig()
              .environment("Pendulum-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                           rollout_fragment_length=64)
              .training(minibatch_size=128, lr=3e-4, gamma=0.99,
                        tau=0.005, updates_per_step=1.0))
    algo = SAC(config)
    best, first = -np.inf, None
    try:
        for _ in range(45):
            r = algo.train()["episode_return_mean"]
            if r is None:
                continue
            first = r if first is None else first
            best = max(best, r)
            if best >= -900:
                break
    finally:
        algo.stop()
    assert best >= -900, (first, best)


def test_multi_learner_same_schedule(ray_start):
    """n=2 learners must run the identical epoch/minibatch schedule as
    n=1 (round-3 weakness: n>1 silently did ONE grad step per update)
    and still learn CartPole."""
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                           rollout_fragment_length=64)
              .training(train_batch_size=512, minibatch_size=128,
                        num_epochs=4, lr=3e-4, entropy_coeff=0.01)
              .learners(num_learners=2))
    algo = config.build()
    try:
        result = algo.train()
        # schedule: epochs * (shard_rows // mb) applied updates
        # shard = 512/2 = 256 rows -> 2 minibatches -> 4 epochs * 2 = 8
        assert result["num_minibatch_updates"] == 8, result
        best = -np.inf
        for _ in range(14):
            r = algo.train()["episode_return_mean"]
            if r is not None:
                best = max(best, r)
        assert best > 50, best
    finally:
        algo.stop()
