"""EnvRunner: actor sampling episodes from gymnasium vector envs
(reference: rllib/env/single_agent_env_runner.py:63 — sample :133; module
forward for action selection runs inside the runner; GAE advantages are
computed here at fragment end so the learner gets ready batches, the role
the reference's learner connector pipeline plays)."""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np


class EnvRunner:
    def __init__(self, config: Dict):
        # rollout workers are CPU-side: a per-step policy forward for a
        # handful of envs is latency-bound, and the learner is where the
        # accelerator belongs (reference: env runners are CPU actors;
        # only Learner workers get GPUs/TPUs). The runtime enforces it: a
        # worker whose lease carries no TPU can open only the CPU backend
        # (_private/worker.py _apply_accelerator_ids).
        import gymnasium as gym
        import jax

        from ray_tpu.rl import envs as _envs   # registers built-in envs
        _envs.register_envs()
        self.cfg = config
        self.n_envs = config["num_envs_per_env_runner"]
        # SAME_STEP autoreset: a done step returns the RESET observation
        # (the true final obs rides in infos), so every recorded
        # transition is real — gymnasium >=1.0's default NextStep mode
        # would interleave a bogus action-ignored reset step into the
        # rollout (stale obs, reward 0) that GAE/vtrace would train on
        self.envs = gym.vector.SyncVectorEnv(
            [lambda: gym.make(config["env"], **config.get("env_config", {}))
             for _ in range(self.n_envs)],
            autoreset_mode=gym.vector.AutoresetMode.SAME_STEP)
        from ray_tpu.rl.connectors import (apply_pipeline, build_pipeline,
                                           pipeline_output_shape)
        from ray_tpu.rl.rl_module import action_spec_of, make_rl_module
        raw_shape = self.envs.single_observation_space.shape
        self._pipeline = build_pipeline(config.get("connectors") or ())
        self._apply_pipeline = apply_pipeline
        obs_shape = pipeline_output_shape(config.get("connectors") or (),
                                          raw_shape)
        self.action_spec = action_spec_of(self.envs.single_action_space)
        self.module = make_rl_module(
            obs_shape, self.action_spec,
            config.get("hidden_sizes", (64, 64)),
            seed=config.get("seed", 0),
            use_lstm=config.get("use_lstm", False))
        # recurrent modules: per-env LSTM carry, zeroed on episode reset
        # (the connector state discipline — rl_module docstring)
        self._state = (self.module.initial_state(self.n_envs)
                       if getattr(self.module, "is_recurrent", False)
                       else None)
        self.rng = jax.random.PRNGKey(config.get("seed", 0)
                                      + config.get("runner_index", 0) * 1000)
        self.obs, _ = self.envs.reset(seed=config.get("seed", 0)
                                      + config.get("runner_index", 0))
        # connected view of the current obs: the module (and therefore
        # the learner's batches) only ever sees pipeline output
        self._cobs = self._apply_pipeline(
            self._pipeline, self.obs.astype(np.float32), is_reset=True)
        self.gamma = config["gamma"]
        self.lam = config["lambda_"]
        self._episode_returns = []
        self._running_returns = np.zeros(self.n_envs)

    def set_weights(self, weights):
        self.module.set_weights(weights)
        return True

    def sample(self, num_steps: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Collect a fragment of num_steps per env; returns flat batch with
        GAE advantages and value targets."""
        import jax
        T = num_steps or self.cfg["rollout_fragment_length"]
        N = self.n_envs
        obs_buf = np.zeros((T, N) + self._cobs.shape[1:], np.float32)
        act_buf = np.zeros((T, N) + self.module.action_event_shape,
                           self.module.action_np_dtype)
        logp_buf = np.zeros((T, N), np.float32)
        rew_buf = np.zeros((T, N), np.float32)
        done_buf = np.zeros((T, N), np.float32)
        val_buf = np.zeros((T, N), np.float32)

        obs = self.obs
        cobs = self._cobs
        for t in range(T):
            self.rng, key = jax.random.split(self.rng)
            action, logp, value = self.module.sample_actions(
                self.module.params, cobs.astype(np.float32), key)
            env_action = (self.module.clip_actions(action)
                          if hasattr(self.module, "clip_actions")
                          else action)
            nxt, rew, term, trunc, _ = self.envs.step(env_action)
            done = np.logical_or(term, trunc)
            obs_buf[t] = cobs
            act_buf[t] = action
            logp_buf[t] = logp
            rew_buf[t] = rew
            done_buf[t] = done.astype(np.float32)
            val_buf[t] = value
            self._running_returns += rew
            for i, d in enumerate(done):
                if d:
                    self._episode_returns.append(self._running_returns[i])
                    self._running_returns[i] = 0.0
            obs = nxt
            cobs = self._apply_pipeline(self._pipeline,
                                        nxt.astype(np.float32),
                                        reset_mask=done)
        self.obs = obs
        self._cobs = cobs

        # bootstrap value for the final obs
        _, last_val = self.module.forward(self.module.params,
                                          cobs.astype(np.float32))
        last_val = np.asarray(last_val)
        adv = np.zeros((T, N), np.float32)
        lastgaelam = np.zeros(N, np.float32)
        for t in reversed(range(T)):
            nonterminal = 1.0 - done_buf[t]
            next_value = val_buf[t + 1] if t + 1 < T else last_val
            delta = rew_buf[t] + self.gamma * next_value * nonterminal \
                - val_buf[t]
            lastgaelam = delta + self.gamma * self.lam * nonterminal \
                * lastgaelam
            adv[t] = lastgaelam
        targets = adv + val_buf

        flat = lambda a: a.reshape((T * N,) + a.shape[2:])  # noqa: E731
        return {"obs": flat(obs_buf), "actions": flat(act_buf),
                "logp": flat(logp_buf), "advantages": flat(adv),
                "value_targets": flat(targets)}

    def sample_trajectory(self, num_steps: Optional[int] = None
                          ) -> Dict[str, np.ndarray]:
        """Time-major fragment [T, N, ...] with behavior log-probs and a
        bootstrap value — the shape V-trace consumes (IMPALA path; the
        reference's equivalent is the env-runner → aggregator episode flow,
        rllib/algorithms/impala/impala.py)."""
        import jax
        T = num_steps or self.cfg["rollout_fragment_length"]
        N = self.n_envs
        obs_buf = np.zeros((T, N) + self._cobs.shape[1:], np.float32)
        act_buf = np.zeros((T, N) + self.module.action_event_shape,
                           self.module.action_np_dtype)
        logp_buf = np.zeros((T, N), np.float32)
        rew_buf = np.zeros((T, N), np.float32)
        done_buf = np.zeros((T, N), np.float32)

        recurrent = self._state is not None
        initial_state = (tuple(np.asarray(s) for s in self._state)
                         if recurrent else None)
        obs = self.obs
        cobs = self._cobs
        for t in range(T):
            self.rng, key = jax.random.split(self.rng)
            if recurrent:
                action, logp, _value, self._state = \
                    self.module.sample_actions(
                        self.module.params, cobs.astype(np.float32), key,
                        self._state)
            else:
                action, logp, _value = self.module.sample_actions(
                    self.module.params, cobs.astype(np.float32), key)
            # step with clipped actions; learn on the unclipped sample
            # (its logp is what the behavior distribution produced)
            env_action = (self.module.clip_actions(action)
                          if hasattr(self.module, "clip_actions")
                          else action)
            nxt, rew, term, trunc, _ = self.envs.step(env_action)
            done = np.logical_or(term, trunc)
            obs_buf[t] = cobs
            act_buf[t] = action
            logp_buf[t] = logp
            rew_buf[t] = rew
            done_buf[t] = done.astype(np.float32)
            self._running_returns += rew
            for i, d in enumerate(done):
                if d:
                    self._episode_returns.append(self._running_returns[i])
                    self._running_returns[i] = 0.0
            if recurrent and done.any():
                # fresh episodes must not see the dead episode's memory
                mask = 1.0 - done.astype(np.float32)[:, None]
                self._state = tuple(np.asarray(s) * mask
                                    for s in self._state)
            obs = nxt
            cobs = self._apply_pipeline(self._pipeline,
                                        nxt.astype(np.float32),
                                        reset_mask=done)
        self.obs = obs
        self._cobs = cobs
        if recurrent:
            _, last_val = self.module.forward(
                self.module.params, cobs.astype(np.float32), self._state)
        else:
            _, last_val = self.module.forward(self.module.params,
                                              cobs.astype(np.float32))
        out = {"obs": obs_buf, "actions": act_buf,
               "behavior_logp": logp_buf, "rewards": rew_buf,
               "dones": done_buf,
               "bootstrap_obs": np.asarray(cobs, np.float32),
               "bootstrap_value": np.asarray(last_val, np.float32)}
        if recurrent:
            # fragment-start carry: the learner re-derives every
            # intermediate state from this + the done flags
            out["initial_state_c"] = initial_state[0]
            out["initial_state_h"] = initial_state[1]
        return out

    def get_metrics(self) -> Dict:
        out = {"episode_return_mean":
               float(np.mean(self._episode_returns[-20:]))
               if self._episode_returns else None,
               "num_episodes": len(self._episode_returns)}
        return out
