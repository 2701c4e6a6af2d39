"""RLlib tranche 2 gates: APPO, recurrent (LSTM) modules, prioritized
replay (reference: rllib/algorithms/appo/appo.py,
rllib/models/torch/recurrent_net.py,
rllib/utils/replay_buffers/prioritized_episode_buffer.py + the
tuned-example regression pattern).

Fast tier: sum-tree / buffer / unroll unit tests. Slow tier: reward-
threshold gates (APPO CartPole, APPO+LSTM on the partially-observable
StatelessCartPole, IMPALA on the built-in pixel env, DQN+prioritized)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rl import AlgorithmConfig, PrioritizedReplayBuffer


RAY_START = dict(num_cpus=4, object_store_memory=256 * 1024 * 1024)


# ----------------------------------------------------------- unit tests
def test_sum_tree_prefix_find():
    from ray_tpu.rl.replay_buffer import SumTree
    t = SumTree(10)
    t.set(np.arange(10), np.arange(10, dtype=np.float64) + 1)
    assert t.total == pytest.approx(55.0)
    # cumulative bounds: [0,1) -> 0, [1,3) -> 1, ..., [45,55) -> 9
    assert t.find(np.array([0.5]))[0] == 0
    assert t.find(np.array([1.5]))[0] == 1
    assert t.find(np.array([44.9]))[0] == 8
    assert t.find(np.array([54.9]))[0] == 9
    t.set(np.array([3]), np.array([0.0]))
    assert t.total == pytest.approx(51.0)


def test_prioritized_buffer_bias_and_weights():
    buf = PrioritizedReplayBuffer(128, seed=3, alpha=1.0, beta=1.0)
    buf.add({"x": np.arange(64, dtype=np.float32)})
    # skew everything tiny except one transition
    buf.update_priorities(np.arange(64), np.full(64, 1e-6))
    buf.update_priorities(np.array([11]), np.array([50.0]))
    s = buf.sample(64)
    assert (s["indices"] == 11).mean() > 0.9
    # the over-sampled transition carries the SMALLEST weight
    others = s["weights"][s["indices"] != 11]
    if len(others):
        assert s["weights"][s["indices"] == 11].max() <= \
            others.min() + 1e-9
    # wraparound write keeps indices in range
    buf.add({"x": np.arange(100, dtype=np.float32)})
    s2 = buf.sample(32)
    assert s2["indices"].max() < 128


def test_recurrent_unroll_matches_stepwise():
    """The learner's scanned unroll must re-derive exactly the states the
    env runner saw, including mid-fragment episode resets (the
    connector state contract)."""
    import jax.numpy as jnp
    from ray_tpu.rl.rl_module import RecurrentDiscreteRLModule
    m = RecurrentDiscreteRLModule(4, 2, (32,), seed=0)
    T, B = 6, 3
    rng = np.random.default_rng(0)
    obs = rng.normal(size=(T, B, 4)).astype(np.float32)
    dones = np.zeros((T, B), np.float32)
    dones[2, 1] = 1.0
    dones[4, 0] = 1.0
    state = m.initial_state(B)
    logits_seq, values_seq = [], []
    for t in range(T):
        state2, (lg, v) = m._step(m.params, state, jnp.asarray(obs[t]))
        logits_seq.append(np.asarray(lg))
        values_seq.append(np.asarray(v))
        mask = 1.0 - dones[t][:, None]
        state = tuple(np.asarray(s) * mask for s in state2)
    resets = np.concatenate([np.zeros((1, B), np.float32), dones[:-1]], 0)
    lg_u, v_u, _ = m._unroll(m.params, m.initial_state(B),
                             jnp.asarray(obs), jnp.asarray(resets))
    np.testing.assert_allclose(np.stack(logits_seq), np.asarray(lg_u),
                               atol=1e-5)
    np.testing.assert_allclose(np.stack(values_seq), np.asarray(v_u),
                               atol=1e-5)


def test_gaussian_recurrent_unroll_matches_stepwise():
    """Same state contract as the discrete LSTM, Gaussian head: the
    scanned unroll re-derives the runner's states across mid-fragment
    resets, and its (mean, log_std) pytree stacks time-major."""
    import jax.numpy as jnp
    from ray_tpu.rl.rl_module import RecurrentContinuousRLModule
    m = RecurrentContinuousRLModule(3, 2, (32,), seed=0)
    T, B = 6, 3
    rng = np.random.default_rng(0)
    obs = rng.normal(size=(T, B, 3)).astype(np.float32)
    dones = np.zeros((T, B), np.float32)
    dones[2, 1] = 1.0
    dones[4, 0] = 1.0
    state = m.initial_state(B)
    means, values = [], []
    for t in range(T):
        state2, ((mean, _ls), v) = m._step(m.params, state,
                                           jnp.asarray(obs[t]))
        means.append(np.asarray(mean))
        values.append(np.asarray(v))
        mask = 1.0 - dones[t][:, None]
        state = tuple(np.asarray(s) * mask for s in state2)
    resets = np.concatenate([np.zeros((1, B), np.float32), dones[:-1]], 0)
    (mean_u, _ls_u), v_u, _ = m._unroll(m.params, m.initial_state(B),
                                        jnp.asarray(obs),
                                        jnp.asarray(resets))
    np.testing.assert_allclose(np.stack(means), np.asarray(mean_u),
                               atol=1e-5)
    np.testing.assert_allclose(np.stack(values), np.asarray(v_u),
                               atol=1e-5)


def test_gaussian_seq_logp_matches_feedforward_contract():
    """The recurrent-continuous module's (dist, actions) -> (logp,
    entropy) must agree with the feedforward ContinuousRLModule's
    logp_entropy_value semantics — both are the same diagonal
    Gaussian."""
    import jax.numpy as jnp
    from ray_tpu.rl.rl_module import (ContinuousRLModule,
                                      RecurrentContinuousRLModule,
                                      make_rl_module)
    m = make_rl_module((3,), {"type": "box", "dim": 2,
                              "low": [-1, -1], "high": [1, 1]},
                       use_lstm=True)
    assert isinstance(m, RecurrentContinuousRLModule)
    ff = ContinuousRLModule(3, 2, (16,), seed=1)
    rng = np.random.default_rng(2)
    obs = jnp.asarray(rng.normal(size=(5, 3)).astype(np.float32))
    acts = jnp.asarray(rng.normal(size=(5, 2)).astype(np.float32))
    logp_ref, ent_ref, _v = ff.logp_entropy_value(ff.params, obs, acts)
    dist, _v2 = ff.dist_values(ff.params, obs)
    logp, ent = ff.seq_logp_entropy(dist, acts)
    np.testing.assert_allclose(np.asarray(logp), np.asarray(logp_ref),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_ref),
                               atol=1e-6)
    # recurrent module shares the same static logp/entropy fn
    assert m.seq_logp_entropy is ff.seq_logp_entropy
    # clip_actions respects the action-spec bounds
    clipped = m.clip_actions(np.array([[2.0, -3.0]], np.float32))
    np.testing.assert_allclose(clipped, [[1.0, -1.0]])


def test_use_lstm_gated_to_vtrace_family(ray_start):
    """use_lstm with PPO must fail loudly at construction (the PPO
    minibatch learner is feedforward-only), and 3D obs with LSTM fail
    at module build (round-5 review findings)."""
    from ray_tpu.rl.rl_module import make_rl_module
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .training(use_lstm=True))
    with pytest.raises(ValueError, match="IMPALA or APPO"):
        config.build()   # PPO
    with pytest.raises(ValueError, match="flat observations"):
        make_rl_module((8, 8, 1), {"type": "discrete", "n": 2},
                       use_lstm=True)


def test_make_replay_buffer_factory():
    from ray_tpu.rl import ReplayBuffer, make_replay_buffer
    assert type(make_replay_buffer({"type": "uniform"}, 10)) is ReplayBuffer
    b = make_replay_buffer({"type": "prioritized", "alpha": 0.5}, 10)
    assert isinstance(b, PrioritizedReplayBuffer) and b.alpha == 0.5
    with pytest.raises(ValueError):
        make_replay_buffer({"type": "nope"}, 10)


# ------------------------------------------------------- threshold gates
def _run_algo_until(algo, stop_reward, max_iters):
    best, first = -np.inf, None
    try:
        for _ in range(max_iters):
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


@pytest.mark.slow
def test_appo_cartpole_threshold(ray_start):
    """APPO gate (reference: tuned_examples/appo/cartpole_appo.py)."""
    from ray_tpu.rl import APPO
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                           rollout_fragment_length=32)
              .training(lr=1e-3, entropy_coeff=0.01, clip_param=0.3,
                        num_epochs=4, target_update_freq=2,
                        vf_loss_coeff=0.5))
    first, best = _run_algo_until(APPO(config), stop_reward=150,
                                  max_iters=90)
    assert best >= 150, (first, best)


@pytest.mark.slow
def test_appo_lstm_repeat_after_me(ray_start):
    """Recurrence gate (reference: rllib repeat_after_me_env tuned
    examples): the reward echoes the PREVIOUS observation's token, so a
    memoryless policy scores chance (~15.5 of 31) — clearing 25 requires
    the LSTM to actually carry state."""
    from ray_tpu.rl import APPO
    config = (AlgorithmConfig()
              .environment("ray_tpu/RepeatAfterMe-v0")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                           rollout_fragment_length=32)
              .training(lr=2e-3, entropy_coeff=0.003, clip_param=0.3,
                        num_epochs=4, hidden_sizes=(64,), use_lstm=True,
                        target_update_freq=2, gamma=0.9))
    first, best = _run_algo_until(APPO(config), stop_reward=25,
                                  max_iters=80)
    assert best >= 25, (first, best)


@pytest.mark.slow
def test_appo_lstm_continuous_repeat_after_me(ray_start):
    """Continuous recurrence gate: reward echoes the PREVIOUS
    observation's target value with a Box action, so a memoryless
    Gaussian policy caps at ~15.5 of 31 (action=0 vs E|target|=0.5) —
    clearing 25 requires the LSTM to carry the observation."""
    from ray_tpu.rl import APPO
    config = (AlgorithmConfig()
              .environment("ray_tpu/ContinuousRepeatAfterMe-v0")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                           rollout_fragment_length=32)
              .training(lr=2e-3, entropy_coeff=0.0, clip_param=0.3,
                        num_epochs=4, hidden_sizes=(64,), use_lstm=True,
                        target_update_freq=2, gamma=0.5))
    first, best = _run_algo_until(APPO(config), stop_reward=25,
                                  max_iters=120)
    assert best >= 25, (first, best)


@pytest.mark.slow
def test_impala_pixel_env_threshold(ray_start):
    """IMPALA conv gate on the built-in pixel env (the Atari-class
    stand-in, BASELINE 'RLlib PPO CartPole/Atari'): random play ~-0.5,
    learned policy clears +0.2."""
    from ray_tpu.rl import IMPALA
    config = (AlgorithmConfig()
              .environment("ray_tpu/GridTarget-v0")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                           rollout_fragment_length=32)
              .training(lr=1e-3, entropy_coeff=0.01, gamma=0.95,
                        num_epochs=2, vf_loss_coeff=0.5))
    first, best = _run_algo_until(IMPALA(config), stop_reward=0.2,
                                  max_iters=80)
    assert best >= 0.2, (first, best)


@pytest.mark.slow
def test_dqn_prioritized_cartpole(ray_start):
    """Prioritized-replay gate: DQN with the prioritized buffer must
    still learn CartPole (and exercises the update_priorities path on
    every grad step)."""
    from ray_tpu.rl import DQN
    config = (AlgorithmConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                           rollout_fragment_length=64)
              .training(lr=1e-3, minibatch_size=64, num_epochs=4,
                        replay_buffer_config={"type": "prioritized",
                                              "alpha": 0.6, "beta": 0.4}))
    first, best = _run_algo_until(DQN(config), stop_reward=120,
                                  max_iters=50)
    assert best >= 120, (first, best)
