"""Env-runner fault tolerance (reference: rllib/utils/actor_manager.py
FaultTolerantActorManager + AlgorithmConfig restart_failed_env_runners):
dead env runners are replaced in-slot mid-training with current weights
re-pushed; the training loop survives on the survivors' data; restarts
are budgeted and disabling them fails fast."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rl import AlgorithmConfig
from ray_tpu.rl.actor_manager import RunnerSetBroken


RAY_START = dict(num_cpus=4, object_store_memory=256 * 1024 * 1024)


def _config(**training):
    return (AlgorithmConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=2,
                         rollout_fragment_length=16)
            .training(**training))


def test_ppo_survives_runner_death(ray_start):
    from ray_tpu.rl import PPO
    algo = PPO(_config())
    try:
        r1 = algo.train()
        assert r1["num_env_steps_sampled"] == 64  # both runners
        victim = algo.env_runners[0]
        ray_tpu.kill(victim)
        r2 = algo.train()  # victim's round drops, slot is refilled
        assert algo.env_runners.num_restarts == 1
        assert len(algo.env_runners) == 2
        assert algo.env_runners[0] is not victim
        # next round: both runners (incl. the replacement) sample again
        r3 = algo.train()
        assert r3["num_env_steps_sampled"] == 64, r3
    finally:
        algo.stop()


def test_impala_survives_runner_death(ray_start):
    from ray_tpu.rl import IMPALA
    algo = IMPALA(_config(lr=1e-3))
    try:
        algo.train()
        victim = algo.env_runners[1]
        ray_tpu.kill(victim)
        algo.train()   # the in-flight fragment surfaces ActorDiedError
        assert algo.env_runners.num_restarts == 1
        assert len(algo.env_runners) == 2
        r3 = algo.train()
        assert r3["num_env_steps_sampled"] > 0
    finally:
        algo.stop()


def test_restarts_disabled_fails_fast(ray_start):
    from ray_tpu.rl import PPO
    algo = PPO(_config(restart_failed_env_runners=False))
    try:
        algo.train()
        ray_tpu.kill(algo.env_runners[0])
        with pytest.raises(RunnerSetBroken, match="disabled"):
            algo.train()
    finally:
        algo.stop()


def test_restart_budget_exhausts(ray_start):
    from ray_tpu.rl import PPO
    algo = PPO(_config(max_env_runner_restarts=1))
    try:
        algo.train()
        ray_tpu.kill(algo.env_runners[0])
        algo.train()                      # consumes the only restart
        assert algo.env_runners.num_restarts == 1
        ray_tpu.kill(algo.env_runners[1])
        with pytest.raises(RunnerSetBroken, match="exhausted"):
            algo.train()
    finally:
        algo.stop()
