"""RL tests: PPO on CartPole improves reward (reference regression-test
pattern: rllib/tuned_examples as threshold tests)."""

import numpy as np

import ray_tpu
from ray_tpu.rl import AlgorithmConfig


RAY_START = dict(num_cpus=6, object_store_memory=128 * 1024 * 1024)


def test_ppo_cartpole_learns(ray_start):
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                           rollout_fragment_length=64)
              .training(train_batch_size=512, minibatch_size=128,
                        num_epochs=4, lr=3e-4, entropy_coeff=0.01))
    algo = config.build()
    first_return = None
    best = -np.inf
    for i in range(20):
        result = algo.train()
        r = result["episode_return_mean"]
        if r is not None:
            if first_return is None:
                first_return = r
            best = max(best, r)
    algo.stop()
    assert first_return is not None
    # CartPole starts ~15-25; PPO should clearly improve within 12 iters
    assert best > first_return + 20, (first_return, best)
    assert best > 50


def test_ppo_multi_learner_smoke(ray_start):
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=1, num_envs_per_env_runner=2,
                           rollout_fragment_length=32)
              .training(train_batch_size=64, minibatch_size=32,
                        num_epochs=1)
              .learners(num_learners=2))
    algo = config.build()
    result = algo.train()
    assert result["num_env_steps_sampled"] == 64
    assert "total_loss" in result
    algo.stop()
