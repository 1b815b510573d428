"""IMPALA: asynchronous sampling with V-trace off-policy correction.

Reference parity: rllib/algorithms/impala/impala.py (the async family the
round-3 verdict called out: sample collection decoupled from the learner
via a queue of in-flight rollouts + periodic async weight broadcast).
Redesigned for this runtime:

- Each EnvRunner keeps ``max_requests_in_flight`` sample() calls pending;
  the driver waits for ANY fragment, hands it straight to the learner, and
  immediately resubmits — the learner never blocks on rollouts, rollouts
  never block on learning.
- Behavior-policy staleness is bounded and *measured*: weight broadcasts
  are fire-and-forget every ``broadcast_interval`` updates, runners stamp
  fragments with the weight version they acted under, and the iteration
  stats report the staleness distribution (the off-policy gap V-trace
  corrects).
- V-trace (Espeholt et al. 2018) runs inside the jitted loss as a reversed
  ``lax.scan`` over the time-major fragment — importance ratios clipped at
  rho_bar/c_bar correct the off-policy value targets and policy gradient.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.algorithm import Algorithm, AlgorithmConfig
from ray_tpu.rllib.env_runner import RolloutBase
from ray_tpu.rllib.learner import Learner, LearnerHyperparams
from ray_tpu.rllib.rl_module import RLModule
from ray_tpu.rllib.sample_batch import SampleBatch

WEIGHTS_VERSION = "weights_version"
BOOTSTRAP_VALUE = "bootstrap_value"


def vtrace(
    behavior_logp,  # [T, N]
    target_logp,  # [T, N]
    rewards,  # [T, N]
    values,  # [T, N]
    bootstrap_value,  # [N]
    terminateds,  # [T, N]
    truncateds,  # [T, N]
    gamma: float,
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
):
    """V-trace targets and policy-gradient advantages (time-major).

    Returns (vs, pg_advantages, mean_rho) — vs/pg_adv are stop-gradiented.
    Terminated steps bootstrap 0. Truncated steps DO bootstrap — with
    next-step autoreset, values[t+1] at a truncation is V(final_obs), the
    correct continuation value — mirroring compute_gae; truncation only
    cuts the scan recursion so corrections never leak across episodes.
    """
    rho = jnp.exp(target_logp - behavior_logp)
    rho_c = jnp.minimum(rho, rho_bar)
    c = jnp.minimum(rho, c_bar)
    not_term = 1.0 - terminateds
    not_done = not_term * (1.0 - truncateds)
    next_values = jnp.concatenate(
        [values[1:], bootstrap_value[None]], axis=0
    )
    delta = rho_c * (rewards + gamma * next_values * not_term - values)

    def scan_fn(carry, x):
        d_t, c_t, nd_t = x
        carry = d_t + gamma * nd_t * c_t * carry
        return carry, carry

    _, vs_minus_v = jax.lax.scan(
        scan_fn,
        jnp.zeros_like(bootstrap_value),
        (delta, c, not_done),
        reverse=True,
    )
    vs = vs_minus_v + values
    vs_next = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    # At a truncation the target bootstraps the raw critic value (the
    # corrected vs[t+1] belongs to the post-reset episode); elsewhere the
    # corrected vs_next is the proper V-trace target.
    boot = jnp.where(truncateds > 0, next_values, vs_next)
    pg_adv = rho_c * (rewards + gamma * boot * not_term - values)
    return (
        jax.lax.stop_gradient(vs),
        jax.lax.stop_gradient(pg_adv),
        jnp.mean(rho),
    )


@dataclasses.dataclass(frozen=True)
class ImpalaParams:
    gamma: float = 0.99
    clip_rho_threshold: float = 1.0
    clip_c_threshold: float = 1.0
    vf_loss_coeff: float = 0.5
    entropy_coeff: float = 0.01


class ImpalaLearner(Learner):
    """One full-fragment gradient step per update (IMPALA does a single
    pass — no epoch shuffling; the minibatch IS the arriving fragment)."""

    def __init__(
        self,
        module: RLModule,
        hps: LearnerHyperparams,
        params: ImpalaParams = ImpalaParams(),
        *,
        group_name: str | None = None,
        world_size: int = 1,
    ):
        super().__init__(
            module, hps, group_name=group_name, world_size=world_size
        )
        self.impala = params

    def loss(self, params, mb):
        p = self.impala
        obs = mb[sb.OBS]  # [T, N, obs_dim]
        T, N = obs.shape[:2]
        mask = mb.get(sb.LOSS_MASK)
        if mask is None:
            mask = jnp.ones((T, N), jnp.float32)
        denom = jnp.sum(mask) + 1e-8

        def mmean(x):
            return jnp.sum(x * mask) / denom

        out = self.module.forward(params, obs.reshape((T * N,) + obs.shape[2:]))
        out = jax.tree.map(lambda a: a.reshape((T, N) + a.shape[1:]), out)
        target_logp = self.module.dist_logp(out, mb[sb.ACTIONS])
        vs, pg_adv, mean_rho = vtrace(
            mb[sb.LOGP],
            target_logp,
            mb[sb.REWARDS],
            out["vf"],
            mb[BOOTSTRAP_VALUE],
            mb[sb.TERMINATEDS],
            mb[sb.TRUNCATEDS],
            gamma=p.gamma,
            rho_bar=p.clip_rho_threshold,
            c_bar=p.clip_c_threshold,
        )
        pi_loss = -mmean(target_logp * pg_adv)
        vf_loss = 0.5 * mmean(jnp.square(out["vf"] - vs))
        entropy = mmean(self.module.dist_entropy(out))
        total = pi_loss + p.vf_loss_coeff * vf_loss - p.entropy_coeff * entropy
        stats = {
            "policy_loss": pi_loss,
            "vf_loss": vf_loss,
            "entropy": entropy,
            "mean_rho": mean_rho,
        }
        return total, stats

    def update(self, batch) -> dict:
        """One gradient step on one time-major fragment dict (replicated
        across the local mesh; IMPALA's per-fragment batches are small —
        the dp win comes from the learner GROUP, not intra-batch dp)."""
        if not self._built:
            self.build()
        mb = {k: jnp.asarray(v) for k, v in batch.items()}
        grads, stats = self._grad(self.params, mb)
        if self._group_name is not None and self._world_size > 1:
            grads = self._allreduce_grads(grads)
        self._apply_grads(grads)
        out = {k: float(v) for k, v in stats.items()}
        out["num_grad_steps"] = 1
        return out


class ImpalaEnvRunner(RolloutBase):
    """Time-major fragment sampler (no GAE — V-trace is the learner's job)
    that stamps each fragment with the weight version it acted under."""

    def __init__(
        self,
        env_maker: Callable,
        module: RLModule,
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
        self._key = jax.random.key(seed * 100003 + worker_index)
        self._weights_version = 0

        @jax.jit
        def _policy_step(params, obs, key):
            out = self.module.forward(params, obs)
            actions = self.module.dist_sample(out, key)
            logp = self.module.dist_logp(out, actions)
            return actions, logp, out["vf"]

        self._policy_step = _policy_step
        self._vf = jax.jit(
            lambda params, obs: self.module.forward(params, obs)["vf"]
        )

    def set_weights(self, params, version: int = 0) -> bool:
        ok = super().set_weights(params)
        self._weights_version = version
        return ok

    def sample(self) -> SampleBatch:
        if self._params is None:
            raise RuntimeError("set_weights() before sample()")
        version = self._weights_version
        T, N = self.fragment_len, self.num_envs
        obs_buf = np.empty((T, N) + self._obs.shape[1:], np.float32)
        act_list, logp_buf = [], np.empty((T, N), np.float32)
        rew_buf = np.empty((T, N), np.float32)
        term_buf = np.empty((T, N), np.float32)
        trunc_buf = np.empty((T, N), np.float32)
        mask_buf = np.empty((T, N), np.float32)
        for t in range(T):
            self._key, k = jax.random.split(self._key)
            obs_in = np.asarray(self._env_to_module(self._obs), np.float32)  # raylint: disable=RL101 -- env-to-module connector output is numpy by contract (rollout buffers + env.step)
            actions, logp, _vf = self._policy_step(self._params, obs_in, k)
            actions_np = np.asarray(actions)  # raylint: disable=RL101 -- policy actions cross the env boundary as numpy
            obs_buf[t] = obs_in
            act_list.append(actions_np)
            logp_buf[t] = np.asarray(logp)  # raylint: disable=RL101 -- logp lands in the numpy rollout buffer; learner re-uploads per batch
            live = ~self._autoreset
            mask_buf[t] = live
            env_actions = (
                np.asarray(self._module_to_env(actions_np))  # raylint: disable=RL101 -- module-to-env connector output feeds env.step (host)
                if len(self._module_to_env)
                else actions_np
            )
            next_obs, rew, term, trunc, _ = self._envs.step(env_actions)
            rew_buf[t] = rew
            term_buf[t] = term
            trunc_buf[t] = trunc
            self._record_episode_step(rew, live, term, trunc)
            self._obs = next_obs
        self._total_steps += int(mask_buf.sum())
        bootstrap = np.asarray(  # raylint: disable=RL101 -- bootstrap value joins the numpy vtrace path
            self._vf(
                self._params,
                np.asarray(  # raylint: disable=RL101 -- frozen obs transform is the numpy vf input at the fragment boundary
                    self._env_to_module(self._obs, update=False), np.float32
                ),
            )
        )
        # Plain dict, NOT SampleBatch: time-major [T, N] columns plus the
        # [N] bootstrap row are deliberately ragged in the leading dim.
        return {
            sb.OBS: obs_buf,
            sb.ACTIONS: np.stack(act_list),
            sb.LOGP: logp_buf,
            sb.REWARDS: rew_buf,
            sb.TERMINATEDS: term_buf,
            sb.TRUNCATEDS: trunc_buf,
            sb.LOSS_MASK: mask_buf,
            BOOTSTRAP_VALUE: bootstrap,
            WEIGHTS_VERSION: np.full((1,), version, np.int64),
        }


@dataclasses.dataclass
class ImpalaConfig(AlgorithmConfig):
    clip_rho_threshold: float = 1.0
    clip_c_threshold: float = 1.0
    vf_loss_coeff: float = 0.5
    entropy_coeff: float = 0.01
    # Async pipeline shape
    max_requests_in_flight_per_env_runner: int = 2
    broadcast_interval: int = 1  # learner updates between weight pushes
    updates_per_iteration: int = 8  # learner updates per train() call

    @property
    def algo_class(self) -> type:
        return Impala

    def impala_params(self) -> ImpalaParams:
        return ImpalaParams(
            gamma=self.gamma,
            clip_rho_threshold=self.clip_rho_threshold,
            clip_c_threshold=self.clip_c_threshold,
            vf_loss_coeff=self.vf_loss_coeff,
            entropy_coeff=self.entropy_coeff,
        )


class Impala(Algorithm):
    learner_cls = ImpalaLearner
    env_runner_cls = ImpalaEnvRunner

    def __init__(self, config: ImpalaConfig):
        if config.num_learners > 1:
            raise NotImplementedError(
                "Impala shards work across env runners, not learners; "
                "use num_learners=1 (the local SPMD learner)"
            )
        import collections

        # Before super().__init__: the base constructor ends with
        # _sync_weights(), which our override reads the version from.
        self._weights_version = 0
        self._updates = 0
        super().__init__(config)
        # Only the last iteration's staleness is reported; a deque keeps
        # memory O(1) over arbitrarily long runs.
        self._staleness: "collections.deque[int]" = collections.deque(
            maxlen=max(config.updates_per_iteration, 1)
        )
        # Prime the pump: every runner keeps `depth` sample() calls pending.
        self._inflight: dict = {}
        depth = config.max_requests_in_flight_per_env_runner
        for r in self.env_runners:
            for _ in range(depth):
                self._inflight[r.sample.remote()] = r

    def env_runner_kwargs(self, config: AlgorithmConfig, i: int) -> dict:
        return dict(
            num_envs=config.num_envs_per_env_runner,
            rollout_fragment_length=config.rollout_fragment_length,
            seed=config.seed,
            worker_index=i,
            env_to_module=config.env_to_module,
            module_to_env=config.module_to_env,
        )

    def learner_loss_args(self) -> tuple:
        return (self.config.impala_params(),)  # type: ignore[attr-defined]

    def extra_state(self) -> dict:
        return {
            "weights_version": self._weights_version,
            "updates": self._updates,
        }

    def apply_extra_state(self, state: dict) -> None:
        self._weights_version = state.get("weights_version", 0)
        self._updates = state.get("updates", 0)

    def _sync_weights(self) -> None:
        """Weight sync stamps the CURRENT version (base stamps 0), so
        fragments sampled after a restore report true staleness."""
        import ray_tpu

        weights = self.learner_group.get_weights()
        ray_tpu.get(
            [
                r.set_weights.remote(weights, self._weights_version)
                for r in self.env_runners
            ]
        )

    def _broadcast_weights_async(self) -> None:
        """Fire-and-forget weight push: the learner does NOT wait for
        runners to apply it (reference: broadcast_interval + async update
        of workers in impala.py). Runners stamp fragments, so staleness
        stays observable."""
        weights = self.learner_group.get_weights()
        self._weights_version += 1
        for r in self.env_runners:
            r.set_weights.remote(weights, self._weights_version)

    def train(self) -> dict:
        import ray_tpu

        cfg = self.config
        t0 = time.perf_counter()
        learn_stats: dict = {}
        steps_this_iter = 0
        wait_s = 0.0
        for _ in range(cfg.updates_per_iteration):
            tw = time.perf_counter()
            ready, _ = ray_tpu.wait(list(self._inflight), num_returns=1)
            wait_s += time.perf_counter() - tw
            fut = ready[0]
            runner = self._inflight.pop(fut)
            batch = ray_tpu.get(fut)
            # Resubmit IMMEDIATELY: the next rollout overlaps this update.
            self._inflight[runner.sample.remote()] = runner
            version = int(batch[WEIGHTS_VERSION][0])
            data = {
                k: v for k, v in batch.items() if k != WEIGHTS_VERSION
            }
            learn_stats = self.learner_group.update(data)
            self._updates += 1
            self._staleness.append(self._weights_version - version)
            steps_this_iter += int(batch[sb.LOSS_MASK].sum())
            if self._updates % cfg.broadcast_interval == 0:
                self._broadcast_weights_async()
        self._total_env_steps += steps_this_iter
        self.iteration += 1
        runner_metrics = ray_tpu.get(
            [r.metrics.remote() for r in self.env_runners]
        )
        rets = [
            m["episode_return_mean"]
            for m in runner_metrics
            if not np.isnan(m["episode_return_mean"])
        ]
        recent = list(self._staleness)
        return {
            "training_iteration": self.iteration,
            "num_env_steps_sampled_lifetime": self._total_env_steps,
            "env_steps_this_iter": steps_this_iter,
            "episode_return_mean": float(np.mean(rets)) if rets else np.nan,
            "learner": learn_stats,
            "weights_version": self._weights_version,
            "staleness_mean": float(np.mean(recent)) if recent else 0.0,
            "staleness_max": int(np.max(recent)) if recent else 0,
            "time_learner_wait_s": round(wait_s, 3),
            "time_iter_s": round(time.perf_counter() - t0, 3),
        }
