"""Env-to-module connector pipeline (reference: rllib/connectors/ —
frame stacking and mean/std observation filters between env and module).
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rl import AlgorithmConfig
from ray_tpu.rl.connectors import (FrameStack, NormalizeObs, apply_pipeline,
                                   build_pipeline, pipeline_output_shape)


def test_frame_stack_shapes_and_history():
    fs = FrameStack(k=3)
    assert fs.output_shape((4,)) == (12,)
    o1 = np.ones((2, 4), np.float32)
    fs.reset(o1)
    out = fs(o1)
    assert out.shape == (2, 12)
    o2 = 2 * np.ones((2, 4), np.float32)
    out = fs(o2)
    # newest frame last; history shifts left
    assert np.allclose(out[:, -4:], 2.0) and np.allclose(out[:, :4], 1.0)


def test_normalize_obs_converges():
    norm = NormalizeObs()
    rng = np.random.default_rng(0)
    out = None
    for _ in range(50):
        out = norm(rng.normal(5.0, 2.0, size=(32, 3)).astype(np.float32))
    assert abs(float(out.mean())) < 0.5
    assert 0.5 < float(out.std()) < 1.5


def test_pipeline_build_and_shape():
    specs = [("frame_stack", {"k": 2}), ("normalize_obs", {})]
    assert pipeline_output_shape(specs, (4,)) == (8,)
    pipe = build_pipeline(specs)
    obs = np.ones((3, 4), np.float32)
    out = apply_pipeline(pipe, obs, is_reset=True)
    assert out.shape == (3, 8)
    with pytest.raises(ValueError):
        build_pipeline([("nope", {})])


RAY_START = dict(num_cpus=4, object_store_memory=128 * 1024 * 1024)


def test_ppo_with_connectors_learns(ray_start):
    """CartPole through frame_stack(2)+normalize: the module input is
    8-dim, batches carry connected obs, and learning still works."""
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                           rollout_fragment_length=64)
              .training(train_batch_size=512, minibatch_size=128,
                        num_epochs=6, lr=3e-4, entropy_coeff=0.01,
                        connectors=(("frame_stack", {"k": 2}),
                                    ("normalize_obs", {}))))
    algo = config.build()
    try:
        assert algo.learner_group.local.module.obs_dim == 8
        best, first = -np.inf, None
        for _ in range(18):
            r = algo.train()["episode_return_mean"]
            if r is None:
                continue
            first = r if first is None else first
            best = max(best, r)
            if best > 80:
                break
        assert best > first + 15 and best > 60, (first, best)
    finally:
        algo.stop()
