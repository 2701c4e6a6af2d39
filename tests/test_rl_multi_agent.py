"""Multi-agent PPO + offline BC (reference: multi_agent_env_runner.py,
rllib/offline/). The toy cooperative env rewards both agents when they
pick matching actions — learnable only if each policy adapts to the
other's behavior through the shared reward."""

import numpy as np

import ray_tpu
from ray_tpu.rl.multi_agent import (MultiAgentConfig, MultiAgentEnv,
                                    MultiAgentPPO)


class _Box:
    def __init__(self, shape):
        self.shape = shape


class _Disc:
    def __init__(self, n):
        self.n = n


class MatchEnv(MultiAgentEnv):
    """Two agents each see one random bit; +1 to both when their actions
    agree with the OTHER agent's observed bit (cooperative coordination)."""

    agents = ["a0", "a1"]

    def __init__(self, episode_len=16):
        self._len = episode_len
        self._t = 0
        self._rng = np.random.default_rng(0)
        self._bits = None

    def observation_space(self, agent_id):
        return _Box((2,))

    def action_space(self, agent_id):
        return _Disc(2)

    def _obs(self):
        # each agent sees its own bit one-hot; the optimal policy copies
        # its own bit (reward checks action == own bit)
        return {aid: np.eye(2, dtype=np.float32)[self._bits[i]]
                for i, aid in enumerate(self.agents)}

    def reset(self, seed=None):
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        self._t = 0
        self._bits = self._rng.integers(0, 2, size=2)
        return self._obs(), {}

    def step(self, actions):
        rew_each = float(actions["a0"] == self._bits[0]) \
            + float(actions["a1"] == self._bits[1])
        rewards = {aid: rew_each / 2.0 for aid in self.agents}
        self._t += 1
        self._bits = self._rng.integers(0, 2, size=2)
        done = self._t >= self._len
        terms = {aid: done for aid in self.agents}
        terms["__all__"] = done
        truncs = {"__all__": False}
        return self._obs(), rewards, terms, truncs, {}


RAY_START = dict(num_cpus=2, object_store_memory=128 * 1024 * 1024)


def test_multi_agent_ppo_learns_cooperative_env(ray_start):
    algo = MultiAgentPPO(MultiAgentConfig(
        env_maker=MatchEnv,
        policy_mapping_fn=lambda aid: aid,      # independent policies
        num_env_runners=2, rollout_fragment_length=64,
        num_epochs=4, minibatch_size=64, lr=3e-3, entropy_coeff=0.0))
    assert sorted(algo.learners) == ["a0", "a1"]
    first = None
    result = None
    for _ in range(12):
        result = algo.train()
        if first is None and result["episode_return_mean"] is not None:
            first = result["episode_return_mean"]
    # perfect coordination = 16 steps * 1.0; random ~8. Require clear
    # improvement over the starting return
    assert result["episode_return_mean"] is not None
    assert result["episode_return_mean"] > first + 2.0, \
        (first, result["episode_return_mean"])


def test_multi_agent_shared_policy(ray_start):
    algo = MultiAgentPPO(MultiAgentConfig(
        env_maker=MatchEnv,
        policy_mapping_fn=lambda aid: "shared",
        num_env_runners=1, rollout_fragment_length=32, num_epochs=2,
        minibatch_size=32))
    assert list(algo.learners) == ["shared"]
    out = algo.training_step()
    assert "shared" in out


def test_bc_trains_from_recorded_dataset(ray_start):
    import ray_tpu.data as rd
    from ray_tpu.rl.offline import BC, BCConfig, record_experiences

    # expert on CartPole-ish synthetic: obs 4-dim random, action = obs[0]>0
    rng = np.random.default_rng(1)
    rows = [{"obs": (o := rng.standard_normal(4).astype(np.float32)).tolist(),
             "action": int(o[0] > 0), "reward": 1.0, "done": False}
            for _ in range(2000)]
    ds = rd.from_items(rows)
    bc = BC(BCConfig(dataset=ds, obs_dim=4, action_dim=2,
                     num_epochs=4, lr=5e-3))
    for _ in range(3):
        out = bc.train()
    assert out["loss"] is not None and out["loss"] < 0.3
    acc = bc.action_accuracy()
    assert acc > 0.9, acc
