"""APPO: asynchronous PPO on the IMPALA pipeline.

Reference parity: rllib/algorithms/appo/appo.py (async sample/learn with a
PPO-clip surrogate + target network). Redesign on this runtime's IMPALA
plumbing (:mod:`ray_tpu.rllib.impala` — decoupled rollouts, weight-version
staleness accounting, fire-and-forget broadcasts):

- **Advantages** come from V-trace computed with the TARGET network's
  policy and values, so the surrogate's baseline doesn't shift under the
  learner every gradient step (the published APPO/IMPACT stabilization).
- **Policy loss** is the PPO clipped surrogate on the current/behavior
  ratio — off-policy fragments are both importance-corrected (V-trace)
  and trust-region-clipped, where plain IMPALA only corrects.
- **Target network** is a hard copy of the learner params every
  ``target_update_freq`` gradient steps; an optional KL(target‖current)
  term regularizes further (off by default, as in the reference).

Everything else (env runners, async train loop, broadcasts, checkpoints)
is inherited from :class:`Impala` unchanged.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.impala import (
    BOOTSTRAP_VALUE,
    Impala,
    ImpalaConfig,
    ImpalaEnvRunner,
    vtrace,
)
from ray_tpu.rllib.learner import Learner, LearnerHyperparams
from ray_tpu.rllib.rl_module import RLModule, to_numpy


@dataclasses.dataclass(frozen=True)
class AppoParams:
    gamma: float = 0.99
    clip_rho_threshold: float = 1.0
    clip_c_threshold: float = 1.0
    clip_param: float = 0.2  # PPO trust region
    vf_loss_coeff: float = 0.5
    entropy_coeff: float = 0.01
    kl_coeff: float = 0.0  # >0 adds KL(target || current)
    target_update_freq: int = 4  # grad steps between target refreshes


class AppoLearner(Learner):
    """One gradient step per arriving fragment (IMPALA cadence) with the
    APPO loss; maintains the target network in learner state."""

    def __init__(
        self,
        module: RLModule,
        hps: LearnerHyperparams,
        params: AppoParams = AppoParams(),
        *,
        group_name: str | None = None,
        world_size: int = 1,
    ):
        super().__init__(
            module, hps, group_name=group_name, world_size=world_size
        )
        self.appo = params

    def build(self) -> bool:
        super().build()
        # Real buffer copies: _apply donates the params buffers, so a
        # by-reference snapshot would alias deleted arrays one step later.
        self.target_params = jax.tree.map(jnp.copy, self.params)
        self._steps_since_target = 0

        def grad_fn(params, target_params, mb):
            (l, stats), g = jax.value_and_grad(
                self._appo_loss, has_aux=True
            )(params, target_params, mb)
            stats = dict(stats, total_loss=l)
            return g, stats

        self._grad_appo = jax.jit(grad_fn)
        return True

    def _appo_loss(self, params, target_params, mb):
        p = self.appo
        obs = mb[sb.OBS]  # [T, N, obs_dim]
        T, N = obs.shape[:2]
        mask = mb.get(sb.LOSS_MASK)
        if mask is None:
            mask = jnp.ones((T, N), jnp.float32)
        denom = jnp.sum(mask) + 1e-8

        def mmean(x):
            return jnp.sum(x * mask) / denom

        flat_obs = obs.reshape((T * N,) + obs.shape[2:])

        def fwd(prm):
            out = self.module.forward(prm, flat_obs)
            return jax.tree.map(
                lambda a: a.reshape((T, N) + a.shape[1:]), out
            )

        out = fwd(params)
        tout = jax.lax.stop_gradient(fwd(target_params))
        cur_logp = self.module.dist_logp(out, mb[sb.ACTIONS])
        tgt_logp = self.module.dist_logp(tout, mb[sb.ACTIONS])

        # V-trace under the TARGET policy/values: stable advantages that
        # do not chase the learner between target refreshes.
        vs, pg_adv, mean_rho = vtrace(
            mb[sb.LOGP],
            tgt_logp,
            mb[sb.REWARDS],
            tout["vf"],
            mb[BOOTSTRAP_VALUE],
            mb[sb.TERMINATEDS],
            mb[sb.TRUNCATEDS],
            gamma=p.gamma,
            rho_bar=p.clip_rho_threshold,
            c_bar=p.clip_c_threshold,
        )
        ratio = jnp.exp(cur_logp - mb[sb.LOGP])
        surr = jnp.minimum(
            ratio * pg_adv,
            jnp.clip(ratio, 1 - p.clip_param, 1 + p.clip_param) * pg_adv,
        )
        pi_loss = -mmean(surr)
        vf_loss = 0.5 * mmean(jnp.square(out["vf"] - vs))
        entropy = mmean(self.module.dist_entropy(out))
        total = pi_loss + p.vf_loss_coeff * vf_loss - p.entropy_coeff * entropy
        kl = mmean(tgt_logp - cur_logp)
        if p.kl_coeff > 0.0:
            total = total + p.kl_coeff * kl
        stats = {
            "policy_loss": pi_loss,
            "vf_loss": vf_loss,
            "entropy": entropy,
            "mean_rho": mean_rho,
            "kl_target_current": kl,
            "clip_frac": mmean(
                (jnp.abs(ratio - 1.0) > p.clip_param).astype(jnp.float32)
            ),
        }
        return total, stats

    def update(self, batch) -> dict:
        if not self._built:
            self.build()
        mb = {k: jnp.asarray(v) for k, v in batch.items()}
        grads, stats = self._grad_appo(self.params, self.target_params, mb)
        if self._group_name is not None and self._world_size > 1:
            grads = self._allreduce_grads(grads)
        self._apply_grads(grads)
        self._steps_since_target += 1
        if self._steps_since_target >= self.appo.target_update_freq:
            self.target_params = jax.tree.map(jnp.copy, self.params)
            self._steps_since_target = 0
        out = {k: float(v) for k, v in stats.items()}
        out["num_grad_steps"] = 1
        return out

    def get_state(self) -> dict:
        state = super().get_state()
        state["target_params"] = to_numpy(self.target_params)
        state["steps_since_target"] = self._steps_since_target
        return state

    def set_state(self, state: dict) -> bool:
        super().set_state(state)
        tp = state.get("target_params")
        self.target_params = (
            jax.device_put(
                jax.tree.map(jnp.asarray, tp), self._replicated
            )
            if tp is not None
            else jax.tree.map(jnp.copy, self.params)
        )
        self._steps_since_target = state.get("steps_since_target", 0)
        return True


@dataclasses.dataclass
class AppoConfig(ImpalaConfig):
    clip_param: float = 0.2
    kl_coeff: float = 0.0
    target_update_freq: int = 4

    @property
    def algo_class(self) -> type:
        return Appo

    def appo_params(self) -> AppoParams:
        return AppoParams(
            gamma=self.gamma,
            clip_rho_threshold=self.clip_rho_threshold,
            clip_c_threshold=self.clip_c_threshold,
            clip_param=self.clip_param,
            vf_loss_coeff=self.vf_loss_coeff,
            entropy_coeff=self.entropy_coeff,
            kl_coeff=self.kl_coeff,
            target_update_freq=self.target_update_freq,
        )


class Appo(Impala):
    """IMPALA's async driver with the APPO learner."""

    learner_cls = AppoLearner
    env_runner_cls = ImpalaEnvRunner

    def learner_loss_args(self) -> tuple:
        return (self.config.appo_params(),)  # type: ignore[attr-defined]
