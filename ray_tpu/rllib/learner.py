"""Learner + LearnerGroup: the gradient-update plane.

Reference parity: rllib/core/learner/learner.py:112 (per-GPU torch Learner)
and learner_group.py:101 (DDP data-parallel learner actors). Redesigned
TPU-first:

- A Learner compiles ONE SPMD update step over a local ``dp`` device mesh
  (minibatch sharded over devices, params replicated); XLA inserts the
  gradient all-reduce over ICI — there is no wrapper class doing collective
  calls per tensor.
- A LearnerGroup of N learner processes splits each train batch N ways and
  all-reduces the *flattened* gradient vector once per SGD step through
  :mod:`ray_tpu.util.collective` (one collective call per step, not one per
  layer — the pytree is raveled into a single contiguous f32 buffer).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable

import jax
import jax.flatten_util  # noqa: F401  (registers jax.flatten_util)
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import MeshSpec, make_mesh
from ray_tpu.rllib.rl_module import RLModule, to_numpy
from ray_tpu.rllib.sample_batch import SampleBatch


@dataclasses.dataclass
class LearnerHyperparams:
    lr: float = 3e-4
    num_sgd_epochs: int = 4
    minibatch_size: int = 256
    grad_clip: float | None = 0.5
    seed: int = 0


class Learner:
    """One learner process: params + optimizer + jitted SPMD update.

    Subclasses define :meth:`loss` (pure function of params/minibatch).
    """

    def __init__(
        self,
        module: RLModule,
        hps: LearnerHyperparams,
        *,
        group_name: str | None = None,
        world_size: int = 1,
    ):
        self.module = module
        self.hps = hps
        self._group_name = group_name
        self._world_size = world_size
        self._built = False
        # The apply step donates params and opt_state. A host read of them
        # from another thread (podracer's supervisor calls get_weights to
        # seed a respawned runner while the learner thread is mid-update)
        # that races the donating dispatch deadlocks jax's CPU runtime, so
        # the two exclude each other.
        self._state_lock = threading.Lock()

    # -- to be implemented by algorithms ------------------------------------
    def loss(self, params, minibatch: dict) -> tuple[jax.Array, dict]:
        raise NotImplementedError

    # -- lifecycle ----------------------------------------------------------
    def build(self) -> bool:
        devices = jax.devices()
        self.mesh = make_mesh(MeshSpec(dp=len(devices)), devices)
        self._batch_sharding = NamedSharding(self.mesh, P("dp"))
        self._replicated = NamedSharding(self.mesh, P())
        self.params = jax.device_put(
            self.module.init(jax.random.key(self.hps.seed)), self._replicated
        )
        tx = [optax.adam(self.hps.lr)]
        if self.hps.grad_clip is not None:
            tx.insert(0, optax.clip_by_global_norm(self.hps.grad_clip))
        self.optimizer = optax.chain(*tx)
        self.opt_state = jax.device_put(
            self.optimizer.init(self.params), self._replicated
        )
        self._rng = np.random.default_rng(self.hps.seed)

        def grad_fn(params, mb):
            (l, stats), g = jax.value_and_grad(self.loss, has_aux=True)(
                params, mb
            )
            stats = dict(stats, total_loss=l)
            return g, stats

        def apply_fn(params, opt_state, grads):
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params
            )
            return optax.apply_updates(params, updates), opt_state

        self._grad = jax.jit(grad_fn)
        self._apply = jax.jit(apply_fn, donate_argnums=(0, 1))
        self._built = True
        return True

    # -- weights ------------------------------------------------------------
    def get_weights(self):
        with self._state_lock:
            return to_numpy(self.params)

    def flat_weights(self):
        """The live params raveled into one contiguous device vector — the
        unit the podracer weight publisher arms on the transfer fabric
        (one buffer per publish, no per-leaf descriptors; consumers
        unravel against their own params structure)."""
        with self._state_lock:
            flat, _ = jax.flatten_util.ravel_pytree(self.params)
        return flat

    def set_weights(self, params) -> bool:
        self.params = jax.device_put(
            jax.tree.map(jnp.asarray, params), self._replicated
        )
        return True

    def get_state(self) -> dict:
        with self._state_lock:
            return {
                "params": to_numpy(self.params),
                "opt_state": to_numpy(self.opt_state),
            }

    def set_state(self, state: dict) -> bool:
        self.params = jax.device_put(
            jax.tree.map(jnp.asarray, state["params"]), self._replicated
        )
        self.opt_state = jax.device_put(
            jax.tree.map(jnp.asarray, state["opt_state"]), self._replicated
        )
        return True

    def ping(self) -> bool:
        return True

    # -- update -------------------------------------------------------------
    def _allreduce_grads(self, grads):
        """Mean the gradient across the learner group as ONE flat vector.

        XLA (and hierarchical-over-XLA) groups take the device path: the
        flat gradient goes into the collective as the jax array it already
        is and comes back device-resident, straight into the jitted
        apply — no device->np.asarray->device bounce per SGD step. Only
        CPU groups (whose data plane is the coordinator actor, host
        arrays by construction) stage through numpy."""
        from ray_tpu.util import collective as col

        flat, unravel = jax.flatten_util.ravel_pytree(grads)
        comm = col.get_group(self._group_name)
        if comm is not None and comm.backend.startswith("xla"):
            reduced = comm.allreduce(flat)
        else:
            reduced = jnp.asarray(
                col.allreduce(np.asarray(flat), self._group_name)  # raylint: disable=RL101 -- cpu-group collectives stage host arrays through the coordinator by construction; xla groups take the device branch above
            )
        return unravel(reduced / self._world_size)

    def _apply_grads(self, grads) -> None:
        """One optimizer step in place (params and opt_state donated)."""
        with self._state_lock:
            self.params, self.opt_state = self._apply(
                self.params, self.opt_state, grads
            )

    def update(self, batch: SampleBatch) -> dict:
        """SGD epochs over shuffled equal-size minibatches. Returns the
        final-minibatch stats plus grad-step count."""
        if not self._built:
            self.build()
        n_dev = len(self.mesh.devices.flat)
        mb_size = max(
            n_dev, (min(self.hps.minibatch_size, len(batch)) // n_dev) * n_dev
        )
        batch = batch.pad_to_multiple(mb_size)
        stats: dict = {}
        steps = 0
        for _ in range(self.hps.num_sgd_epochs):
            shuffled = batch.shuffled(self._rng)
            for mb in shuffled.minibatches(mb_size):
                mb_dev = jax.device_put(dict(mb), self._batch_sharding)
                grads, stats = self._grad(self.params, mb_dev)
                if self._group_name is not None and self._world_size > 1:
                    grads = self._allreduce_grads(grads)
                self._apply_grads(grads)
                steps += 1
        out = {k: float(v) for k, v in stats.items()}
        out["num_grad_steps"] = steps
        return out


class LearnerGroup:
    """N data-parallel learners.

    n == 1: the learner lives in-process (driver) — the TPU path, where one
    process drives the whole local mesh. n > 1: learner actors joined into a
    collective group; each update() splits the batch and runs concurrently.
    """

    def __init__(
        self,
        learner_cls: type,
        module: RLModule,
        hps: LearnerHyperparams,
        *,
        num_learners: int = 1,
        learner_resources: dict | None = None,
        backend: str = "cpu",
        group_name: str = "learner_group",
        loss_args: tuple = (),
    ):
        import ray_tpu

        self.num_learners = num_learners
        if num_learners <= 1:
            self._local = learner_cls(module, hps, *loss_args)
            self._local.build()
            self._actors = []
            return
        self._local = None
        self._actors = [
            ray_tpu.remote(learner_cls)
            .options(**(learner_resources or {"num_cpus": 1}))
            .remote(
                module,
                hps,
                *loss_args,
                group_name=group_name,
                world_size=num_learners,
            )
            for _ in range(num_learners)
        ]
        from ray_tpu.util import collective as col

        col.create_collective_group(
            self._actors,
            num_learners,
            list(range(num_learners)),
            backend=backend,
            group_name=group_name,
        )
        ray_tpu.get([a.build.remote() for a in self._actors])

    def update(self, batch: SampleBatch) -> dict:
        import ray_tpu

        if self._local is not None:
            return self._local.update(batch)
        n = self.num_learners
        batch = batch.pad_to_multiple(n)
        shard = len(batch) // n
        refs = [
            a.update.remote(
                SampleBatch(
                    {k: v[i * shard : (i + 1) * shard] for k, v in batch.items()}
                )
            )
            for i, a in enumerate(self._actors)
        ]
        results = ray_tpu.get(refs)
        return results[0]

    def get_weights(self):
        import ray_tpu

        if self._local is not None:
            return self._local.get_weights()
        return ray_tpu.get(self._actors[0].get_weights.remote())

    def flat_weights(self):
        import ray_tpu

        if self._local is not None:
            return self._local.flat_weights()
        return ray_tpu.get(self._actors[0].flat_weights.remote())

    def update_device(self, cols: dict) -> dict:
        """Device-resident minibatch update (podracer learner plane).

        In-process (TPU-path) learner: the columns go straight into the
        jitted step. Actor group (n > 1): each actor takes a contiguous
        dim0 shard of the minibatch over RPC (the host hop is inherent to
        actor learners — the data plane is host arrays by construction),
        runs the SAME jitted step, and the per-step flat-gradient
        allreduce keeps every replica's params identical; rank 0's stats
        come back. Replica equality with the single-learner full-batch
        step holds for mean-based losses with equal shards (mean of
        equal-size shard-means == full-batch mean)."""
        if self._local is not None:
            return self._local.update_device(cols)
        import numpy as np

        import ray_tpu

        n = self.num_learners
        rows = min(len(v) for v in cols.values())
        if rows % n:
            raise ValueError(
                f"update_device minibatch dim0 {rows} is not divisible by "
                f"num_learners {n}; gradient means would diverge across "
                f"replicas"
            )
        shard = rows // n
        host = {k: np.asarray(v) for k, v in cols.items()}  # raylint: disable=RL101 -- actor learners receive host arrays over RPC by construction; the device stream ends at the group boundary
        refs = [
            a.update_device.remote(
                {k: v[i * shard : (i + 1) * shard] for k, v in host.items()}
            )
            for i, a in enumerate(self._actors)
        ]
        results = ray_tpu.get(refs)
        return results[0]

    def get_state(self) -> dict:
        import ray_tpu

        if self._local is not None:
            return self._local.get_state()
        return ray_tpu.get(self._actors[0].get_state.remote())

    def set_state(self, state: dict) -> None:
        import ray_tpu

        if self._local is not None:
            self._local.set_state(state)
        else:
            ray_tpu.get([a.set_state.remote(state) for a in self._actors])

    def shutdown(self) -> None:
        import ray_tpu

        for a in self._actors:
            try:
                ray_tpu.kill(a)
            except Exception:  # raylint: disable=RL006 -- teardown kill; aggregator already dead
                pass
