"""DQN: off-policy value learning over the shared Learner/EnvRunner plane.

Reference parity: rllib/algorithms/dqn/ (DQN + DQNRainbowLearner, double-Q
and target network; torch). Redesign notes:

- The TD targets are computed ONCE per replay batch with the frozen target
  network — a jitted double-Q step — and ride the batch as a plain column;
  the Learner's loss is then a pure regression, so the base class's jitted
  SPMD update (dp-sharded minibatch, XLA-collective gradient mean) is
  reused verbatim. No PPO shape leaks into the shared plumbing (round-2
  verdict: prove Learner/LearnerGroup aren't PPO-shaped).
- Exploration is epsilon-greedy on the runners (annealed driver-side);
  rollouts collect raw transitions (obs, action, reward, next_obs, done) —
  no GAE — which flow through a ReplayBuffer ACTOR, not straight to the
  learner.
- The target network refreshes every ``target_network_update_freq`` grad
  steps (hard update, as the reference's default).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.algorithm import Algorithm, AlgorithmConfig
from ray_tpu.rllib.env_runner import RolloutBase
from ray_tpu.rllib.learner import Learner, LearnerHyperparams
from ray_tpu.rllib.replay_buffer import ReplayBuffer
from ray_tpu.rllib.rl_module import (
    RLModule,
    _mlp_apply,
    _mlp_init,
    to_numpy,
)
from ray_tpu.rllib.sample_batch import SampleBatch

TD_TARGETS = "td_targets"


@dataclasses.dataclass(frozen=True)
class QModule(RLModule):
    """Q-network: obs -> Q(s, a) for each discrete action."""

    obs_dim: int
    num_actions: int
    hidden: Sequence[int] = (64, 64)

    def init(self, key: jax.Array):
        return {
            "q": _mlp_init(
                key,
                [self.obs_dim, *self.hidden, self.num_actions],
                scale_last=0.01,
            )
        }

    def forward(self, params, obs: jax.Array) -> dict:
        obs = obs.astype(jnp.float32)
        if obs.ndim > 2:
            obs = obs.reshape(obs.shape[0], -1)
        return {"q": _mlp_apply(params["q"], obs)}


class DQNEnvRunner(RolloutBase):
    """Epsilon-greedy transition collector (reference:
    single_agent_env_runner with EpsilonGreedy exploration). Shares the
    vector-env + autoreset + episode-accounting machinery with the
    on-policy EnvRunner via RolloutBase; only action selection and the
    emitted columns differ (raw transitions for replay, no GAE)."""

    def __init__(
        self,
        env_maker,
        module: QModule,
        *,
        num_envs: int = 1,
        rollout_fragment_length: int = 64,
        seed: int = 0,
        worker_index: int = 0,
        env_to_module=None,
        module_to_env=None,
    ):
        super().__init__(
            env_maker,
            module,
            num_envs=num_envs,
            rollout_fragment_length=rollout_fragment_length,
            seed=seed,
            worker_index=worker_index,
            env_to_module=env_to_module,
            module_to_env=module_to_env,
        )
        self._rng = np.random.default_rng(seed * 99991 + worker_index)
        self._epsilon = 1.0

        @jax.jit
        def greedy(params, obs):
            return jnp.argmax(self.module.forward(params, obs)["q"], axis=-1)

        self._greedy = greedy

    def set_epsilon(self, epsilon: float) -> bool:
        self._epsilon = float(epsilon)
        return True

    def greedy_actions(self, obs_in: np.ndarray) -> np.ndarray:
        """Greedy (exploitation) actions for one connected-obs batch.
        The podracer runner overrides this to route through the inference
        tier; exploration stays local either way."""
        return np.asarray(self._greedy(self._params, obs_in))  # raylint: disable=RL101 -- greedy actions cross the env boundary as numpy (same contract as the on-policy runner)

    def sample(self) -> SampleBatch:
        """One [T*N] fragment of transitions, autoreset dummy steps already
        filtered out (replay must never store fabricated rows)."""
        if self._params is None:
            raise RuntimeError("set_weights() before sample()")
        T, N = self.fragment_len, self.num_envs
        n_act = self.module.num_actions
        obs_rows, act_rows, rew_rows = [], [], []
        next_rows, term_rows = [], []
        for _ in range(T):
            obs_in = np.asarray(
                self._env_to_module(self._obs), np.float32
            )
            greedy = self.greedy_actions(obs_in)
            explore = self._rng.random(N) < self._epsilon
            actions = np.where(
                explore, self._rng.integers(0, n_act, size=N), greedy
            ).astype(greedy.dtype)
            live = ~self._autoreset
            env_actions = (
                np.asarray(self._module_to_env(actions))
                if len(self._module_to_env)
                else actions
            )
            next_obs, rew, term, trunc, _ = self._envs.step(env_actions)
            # next_obs on a done step is the episode's FINAL observation
            # (gymnasium NEXT_STEP autoreset resets one step later); the
            # terminal flag gates bootstrapping in the TD target, and the
            # following dummy reset row is dropped via `live`. Replay
            # stores CONNECTED observations (frozen for next_obs: that
            # same obs updates stats when it leads the next step).
            next_in = np.asarray(
                self._env_to_module(next_obs, update=False), np.float32
            )
            obs_rows.append(obs_in[live])
            act_rows.append(actions[live])
            rew_rows.append(rew[live])
            next_rows.append(next_in[live])
            term_rows.append(term[live])
            self._record_episode_step(rew, live, term, trunc)
            self._obs = next_obs
        batch = SampleBatch(
            {
                sb.OBS: np.concatenate(obs_rows).astype(np.float32),
                sb.ACTIONS: np.concatenate(act_rows),
                sb.REWARDS: np.concatenate(rew_rows).astype(np.float32),
                sb.NEXT_OBS: np.concatenate(next_rows).astype(np.float32),
                sb.TERMINATEDS: np.concatenate(term_rows).astype(np.float32),
            }
        )
        self._count_env_steps(len(batch))
        return batch


@dataclasses.dataclass(frozen=True)
class DQNParams:
    gamma: float = 0.99
    double_q: bool = True
    target_network_update_freq: int = 500  # in grad steps
    huber_delta: float = 1.0


class DQNLearner(Learner):
    """TD regression on precomputed double-Q targets + target network."""

    def __init__(
        self,
        module: QModule,
        hps: LearnerHyperparams,
        dqn: DQNParams = DQNParams(),
        *,
        group_name: str | None = None,
        world_size: int = 1,
    ):
        super().__init__(
            module, hps, group_name=group_name, world_size=world_size
        )
        self.dqn = dqn

    def build(self) -> bool:
        super().build()
        # REAL copies: the base update donates the params buffers to the
        # jitted apply; aliased target buffers would be invalidated.
        self.target_params = jax.tree.map(jnp.copy, self.params)
        self._grad_steps = 0

        def td_targets(params, target_params, next_obs, rewards, terms):
            q_target = self.module.forward(target_params, next_obs)["q"]
            if self.dqn.double_q:
                # Double-Q: online net selects, target net evaluates.
                best = jnp.argmax(
                    self.module.forward(params, next_obs)["q"], axis=-1
                )
            else:
                best = jnp.argmax(q_target, axis=-1)
            q_next = jnp.take_along_axis(
                q_target, best[..., None], axis=-1
            )[..., 0]
            return rewards + self.dqn.gamma * (1.0 - terms) * q_next

        self._td_targets = jax.jit(td_targets)
        return True

    def loss(self, params, mb):
        q = self.module.forward(params, mb[sb.OBS])["q"]
        q_a = jnp.take_along_axis(
            q, mb[sb.ACTIONS][..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        err = q_a - mb[TD_TARGETS]
        delta = self.dqn.huber_delta
        huber = jnp.where(
            jnp.abs(err) <= delta,
            0.5 * jnp.square(err),
            delta * (jnp.abs(err) - 0.5 * delta),
        )
        total = jnp.mean(huber)
        stats = {
            "mean_q": jnp.mean(q_a),
            "mean_td_error": jnp.mean(jnp.abs(err)),
            "max_q": jnp.max(q),
        }
        return total, stats

    def update(self, batch: SampleBatch) -> dict:
        if not self._built:
            self.build()
        batch = SampleBatch(dict(batch))
        batch[TD_TARGETS] = np.asarray(  # raylint: disable=RL101 -- TD targets re-enter the numpy SampleBatch replay path; minibatch slicing is host-side by design
            self._td_targets(
                self.params,
                self.target_params,
                jnp.asarray(batch[sb.NEXT_OBS]),
                jnp.asarray(batch[sb.REWARDS]),
                jnp.asarray(batch[sb.TERMINATEDS]),
            )
        )
        stats = super().update(batch)
        self._maybe_refresh_target(stats.get("num_grad_steps", 0), stats)
        return stats

    def _maybe_refresh_target(self, grad_steps: int, stats: dict) -> None:
        self._grad_steps += grad_steps
        if self._grad_steps >= self.dqn.target_network_update_freq:
            self._grad_steps = 0
            # Hard refresh (reference default); learners in a group apply
            # the same schedule to identical params, so targets stay
            # equal. jnp.copy: donated-buffer aliasing, see build().
            self.target_params = jax.tree.map(jnp.copy, self.params)
            stats["target_net_updated"] = 1.0

    def update_device(self, cols: dict) -> dict:
        """One minibatch TD step with every operand device-resident — the
        podracer learner plane's consume path (round-13 contract: no host
        SampleBatch staging between the trajectory stream and the jitted
        update). ``cols`` holds jax arrays keyed by the replay columns;
        the minibatch is placed under the dp sharding, TD targets stay on
        device, and the returned stats are device scalars the caller
        reads back at its own cadence."""
        if not self._built:
            self.build()
        # The stream's arrays arrive committed to one device (the replay
        # ring's); re-lay them out under the dp sharding FIRST — params
        # are mesh-replicated and jit refuses mixed committed device sets.
        cols = jax.device_put(dict(cols), self._batch_sharding)
        targets = self._td_targets(
            self.params,
            self.target_params,
            cols[sb.NEXT_OBS],
            cols[sb.REWARDS],
            cols[sb.TERMINATEDS],
        )
        mb = {
            sb.OBS: cols[sb.OBS],
            sb.ACTIONS: cols[sb.ACTIONS],
            TD_TARGETS: targets,
        }
        grads, stats = self._grad(self.params, mb)
        if self._group_name is not None and self._world_size > 1:
            grads = self._allreduce_grads(grads)
        self._apply_grads(grads)
        out = dict(stats)
        self._maybe_refresh_target(1, out)
        return out

    def get_state(self) -> dict:
        state = super().get_state()
        state["target_params"] = to_numpy(self.target_params)
        state["grad_steps_since_target_sync"] = self._grad_steps
        return state

    def set_state(self, state: dict) -> bool:
        super().set_state(state)
        if "target_params" in state:
            self.target_params = jax.device_put(
                jax.tree.map(jnp.asarray, state["target_params"]),
                self._replicated,
            )
            self._grad_steps = state.get("grad_steps_since_target_sync", 0)
        else:  # restored from a pre-target checkpoint
            self.target_params = jax.tree.map(jnp.copy, self.params)
        return True


@dataclasses.dataclass
class DQNConfig(AlgorithmConfig):
    # Off-policy defaults (override the on-policy base values).
    lr: float = 5e-4
    num_sgd_epochs: int = 1  # one pass over each sampled train batch
    # exploration schedule (linear anneal by lifetime env steps)
    epsilon_initial: float = 1.0
    epsilon_final: float = 0.05
    epsilon_anneal_steps: int = 5_000
    # replay
    replay_buffer_capacity: int = 50_000
    learning_starts: int = 500  # env steps before the first update
    train_batch_size: int = 64
    num_train_batches_per_iteration: int = 16
    # dqn
    double_q: bool = True
    target_network_update_freq: int = 200

    @property
    def algo_class(self) -> type:
        return DQN

    def hyperparams(self) -> LearnerHyperparams:
        # minibatch_size derives from train_batch_size AT USE TIME (fluent
        # setters don't re-run __post_init__-style derivations).
        hps = super().hyperparams()
        return dataclasses.replace(
            hps, minibatch_size=self.train_batch_size
        )

    def dqn_params(self) -> DQNParams:
        return DQNParams(
            gamma=self.gamma,
            double_q=self.double_q,
            target_network_update_freq=self.target_network_update_freq,
        )


class DQN(Algorithm):
    learner_cls = DQNLearner
    env_runner_cls = DQNEnvRunner

    def __init__(self, config: DQNConfig):
        import ray_tpu

        super().__init__(config)
        self.replay = ray_tpu.remote(ReplayBuffer).remote(
            capacity=config.replay_buffer_capacity, seed=config.seed
        )

    def default_module(self, maker, config) -> QModule:
        env = maker()
        try:
            obs_dim = int(np.prod(env.observation_space.shape))
            if not hasattr(env.action_space, "n"):
                raise ValueError("DQN supports discrete action spaces only")
            num_actions = int(env.action_space.n)
        finally:
            env.close()
        return QModule(
            obs_dim=obs_dim,
            num_actions=num_actions,
            hidden=tuple(config.hidden),
        )

    def learner_loss_args(self) -> tuple:
        return (self.config.dqn_params(),)  # type: ignore[attr-defined]

    def env_runner_kwargs(self, config, i: int) -> dict:
        return dict(
            num_envs=config.num_envs_per_env_runner,
            rollout_fragment_length=config.rollout_fragment_length,
            seed=config.seed,
            worker_index=i,
            env_to_module=config.env_to_module,
            module_to_env=config.module_to_env,
        )

    def _epsilon(self) -> float:
        c = self.config
        frac = min(1.0, self._total_env_steps / max(1, c.epsilon_anneal_steps))
        return c.epsilon_initial + frac * (c.epsilon_final - c.epsilon_initial)

    def train(self) -> dict:
        """One iteration: explore -> replay.add -> K sampled updates ->
        weight sync (reference: DQN training_step)."""
        import time

        import ray_tpu

        c = self.config
        eps = self._epsilon()
        ray_tpu.get([r.set_epsilon.remote(eps) for r in self.env_runners])
        t0 = time.perf_counter()
        batches = ray_tpu.get([r.sample.remote() for r in self.env_runners])
        batch = SampleBatch.concat(batches)
        t_sample = time.perf_counter() - t0
        buffer_size = ray_tpu.get(self.replay.add.remote(batch))
        self._total_env_steps += len(batch)

        learn_stats: dict = {}
        t0 = time.perf_counter()
        # Gate on LIFETIME steps, not buffer size: a small ring buffer caps
        # size below learning_starts and must not disable training forever.
        if self._total_env_steps >= c.learning_starts:
            # ONE buffer round-trip per iteration: uniform-with-replacement
            # sampling makes K batches of B equal in distribution to one
            # sample of K*B chunked driver-side.
            k = c.num_train_batches_per_iteration
            rows = ray_tpu.get(
                self.replay.sample.remote(k * c.train_batch_size)
            )
            for train_batch in rows.minibatches(c.train_batch_size):
                learn_stats = self.learner_group.update(train_batch)
            self._sync_weights()
        t_learn = time.perf_counter() - t0

        self.iteration += 1
        runner_metrics = ray_tpu.get(
            [r.metrics.remote() for r in self.env_runners]
        )
        rets = [
            m["episode_return_mean"]
            for m in runner_metrics
            if not np.isnan(m["episode_return_mean"])
        ]
        return {
            "training_iteration": self.iteration,
            "num_env_steps_sampled_lifetime": self._total_env_steps,
            "env_steps_this_iter": len(batch),
            "episode_return_mean": float(np.mean(rets)) if rets else np.nan,
            "epsilon": eps,
            "replay_buffer_size": buffer_size,
            "learner": learn_stats,
            "time_sample_s": round(t_sample, 3),
            "time_learn_s": round(t_learn, 3),
        }

    # -- checkpointing: the buffer is part of DQN's state --------------------

    def save(self, path: str) -> str:
        import pickle

        import ray_tpu

        super().save(path)
        with open(os.path.join(path, "replay_buffer.pkl"), "wb") as f:
            pickle.dump(ray_tpu.get(self.replay.get_state.remote()), f)
        return path

    def restore(self, path: str) -> None:
        import pickle

        import ray_tpu

        super().restore(path)
        buf_path = os.path.join(path, "replay_buffer.pkl")
        if os.path.exists(buf_path):
            with open(buf_path, "rb") as f:
                ray_tpu.get(self.replay.set_state.remote(pickle.load(f)))
        else:
            # Pre-buffer checkpoint: the restored step counter would pin
            # epsilon at its floor over an EMPTY buffer — re-warm
            # exploration instead of exploiting unseasoned Q-values.
            self._total_env_steps = 0

    def stop(self) -> None:
        import ray_tpu

        super().stop()
        try:
            ray_tpu.kill(self.replay)
        except Exception:  # raylint: disable=RL006 -- teardown kill; replay actor already dead
            pass
