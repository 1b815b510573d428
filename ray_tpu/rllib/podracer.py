"""Podracer-style decoupled RL: actor / inference / learner planes.

Reference: "Podracer architectures for scalable Reinforcement Learning"
(PAPERS.md) — the Sebulba shape: env-stepping actors batch observation
requests into an inference tier while learner devices consume a
device-resident trajectory stream; "Exploring the limits of Concurrency
in ML Training on Google TPUs" motivates keeping the learner path free
of host round-trips. This module turns the single-loop DQN
(sample → replay.add → K updates → weight sync, one phase at a time)
into five concurrent planes on top of the existing core:

- **Acting plane** — :class:`PodracerEnvRunner` actors step vector envs
  and collect epsilon-greedy transitions (exploration RNG stays local).
- **Inference tier** — :class:`InferenceServer` actors coalesce greedy
  requests from many runners into fixed-shape jitted device batches
  under a batching-window/size knob (``raytpu_rl_inference_batch_size``
  is the coalescing histogram).
- **Trajectory plane** — runners stage each fragment's columns on the
  transfer fabric (:meth:`_Fabric.arm_group`: one uid, one pull) and
  push the descriptor into a bounded
  queue; the learner pulls fragments device-to-device into a
  :class:`~ray_tpu.rllib.replay_buffer.DeviceReplay` ring and updates
  through :meth:`DQNLearner.update_device` — no host SampleBatch staging
  between the stream and the jitted step (the round-13 contract), and
  the round-11 hierarchical collectives serve a learner group's
  allreduce unchanged. A full queue IS the backpressure
  (``raytpu_rl_replay_occupancy`` gauges both planes).
- **Weight-sync plane** — :class:`WeightPublisher` versions the learner
  params and arms serve-once flat vectors on the fabric; consumers pull
  in place (:meth:`RolloutBase.apply_weights`). The ``weightsync`` fault
  site severs a pull: the consumer keeps last-good params and the
  version lag is counted (``raytpu_rl_weight_version_lag``).
- **Supervision** — a seeded ``envrun.kill`` fault (or a real crash)
  takes a runner down mid-rollout; the driver supervisor respawns it and
  the queue never wedges (dead producers' staged entries fail the pull
  and are dropped, serve-once entries TTL-evict). A dead inference
  replica surfaces as a failed weight apply: the learner respawns it
  seeded with current params (``replica_restarts`` in the run result),
  so the staleness gate never wedges on a corpse.

**Staleness contract**: ``podracer_staleness_steps`` bounds how many
published versions the slowest inference replica may trail the learner;
the learner gates on it after each publish. Staleness **0 degenerates to
lockstep** — ``train()`` runs the exact single-loop DQN iteration (same
seed ⇒ bit-identical params trajectory, CI-pinned by
tests/test_rllib_podracer.py) with only the weight sync riding the
fabric (value-identical: f32 ravel/unravel round-trips exactly).

**Kill switch**: ``RAY_TPU_PODRACER=0`` (and simply not using this API)
leaves existing algorithms byte-identical; under the switch,
:meth:`PodracerDQN.run` falls back to looping the single-loop iteration
— the A/B baseline of ``tools/ray_perf.py --rl-only --no-podracer``.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.algorithm import _env_maker
from ray_tpu.rllib.dqn import DQN, DQNConfig, DQNEnvRunner
from ray_tpu.rllib.env_runner import FabricWeightConsumer
from ray_tpu.rllib.replay_buffer import pow2_bucket
from ray_tpu.rllib.sample_batch import SampleBatch
from ray_tpu.util import metrics as _metrics

_INFER_BATCH = _metrics.Histogram(
    "raytpu_rl_inference_batch_size",
    "coalesced rows per inference-tier forward (pre-padding): the "
    "batching-window/size knob's effectiveness",
    boundaries=[1, 2, 4, 8, 16, 32, 64, 128, 256],
)
_WEIGHT_LAG = _metrics.Gauge(
    "raytpu_rl_weight_version_lag",
    "published learner version minus the slowest consumer's applied "
    "version (bounded by podracer_staleness_steps)",
)


def podracer_enabled() -> bool:
    """RAY_TPU_PODRACER kill switch (cluster knob)."""
    return GLOBAL_CONFIG.podracer


# -- trajectory plane ---------------------------------------------------------

# Column order is part of the wire contract: descriptors carry arrays
# positionally (one uid per fragment).
_COLUMNS = (sb.OBS, sb.ACTIONS, sb.REWARDS, sb.NEXT_OBS, sb.TERMINATEDS)


def stage_fragment(batch: SampleBatch) -> tuple[dict, int]:
    """Stage one fragment's columns on the transfer fabric (single arm,
    single pull). Returns (queue entry, armed uid — for producer-side
    release hygiene).

    Columns pad to a power-of-two row bucket HERE, while they are still
    host numpy (DQN fragments drop autoreset rows, so raw sizes vary
    per rollout): the fabric then arms a handful of wire shapes and the
    learner's :meth:`DeviceReplay.add` scatter compiles once per bucket
    instead of once per novel fragment size — a mid-run XLA compile
    stalls the learner plane for ~10-30 ms, which is the whole round's
    update budget. ``steps`` carries the valid row count."""
    from ray_tpu.experimental import transfer as xfer

    n = len(batch)
    bucket = pow2_bucket(n)
    arrays = []
    for k in _COLUMNS:
        v = np.asarray(batch[k])
        if bucket > n:
            pad = np.zeros((bucket - n,) + v.shape[1:], v.dtype)
            v = np.concatenate([v, pad], axis=0)
        arrays.append(jnp.asarray(v))
    desc = xfer.fabric().arm_group(arrays)
    return {"desc": desc, "steps": n}, desc["uuid"]


def load_fragment(entry: dict):
    """Pull one staged fragment device-to-device; ``None`` when the
    producer died mid-flight (the queue must not wedge on its corpse —
    the entry is simply dropped and counted)."""
    from ray_tpu.experimental import transfer as xfer

    try:
        arrays = xfer.fabric().pull_group(entry["desc"])
    except Exception:  # raylint: disable=RL006 -- dead-producer pull: dropping the fragment IS the no-wedge contract; the caller counts it
        xfer.fabric().count_fallback()
        return None
    return dict(zip(_COLUMNS, arrays))


# -- inference tier -----------------------------------------------------------


class InferenceServer(FabricWeightConsumer):
    """Inference-tier actor: coalesces greedy-action requests from many
    env-runner actors into fixed-shape jitted device batches.

    Requests arriving within one batching window (or until the row cap
    trips) concatenate into a single forward, padded to a power-of-two
    bucket so only a handful of shapes ever compile; results split back
    per caller. Run with ``max_concurrency`` so requests overlap the
    window. Weights are versioned and pulled in place over the fabric
    (the :class:`~ray_tpu.rllib.env_runner.FabricWeightConsumer`
    contract shared with the rollout plane; the mixin's race guard
    matters HERE, where ``max_concurrency`` runs applies concurrently).
    """

    def __init__(
        self,
        module,
        batch_window_s: float = 0.002,
        max_batch: int = 64,
    ):
        self.module = module
        self._window = float(batch_window_s)
        self._max = int(max_batch)
        self._init_weight_sync()
        self._pending: list = []
        self._flush_task = None
        self.stats = {
            "requests": 0,
            "batches": 0,
            "rows": 0,
            "max_batch_rows": 0,
        }

        @jax.jit
        def greedy(params, obs):
            return jnp.argmax(self.module.forward(params, obs)["q"], axis=-1)

        self._greedy = greedy

    # -- weights --------------------------------------------------------------

    def _install_params(self, params) -> None:
        self._params = jax.tree.map(jnp.asarray, params)

    def set_weights(self, params) -> bool:
        self._install_params(params)
        self._unravel = None
        return True

    # -- the batching path ----------------------------------------------------

    async def infer(self, obs) -> np.ndarray:
        """Greedy actions for one connected-obs batch; coalesced with
        concurrent callers inside the batching window."""
        import asyncio

        obs = np.asarray(obs, np.float32)
        fut = asyncio.get_running_loop().create_future()
        self._pending.append((obs, fut))
        self.stats["requests"] += 1
        rows = sum(len(o) for o, _ in self._pending)
        if rows >= self._max:
            self._flush()
        elif self._flush_task is None or self._flush_task.done():
            from ray_tpu.util.tasks import spawn

            self._flush_task = spawn(
                self._flush_after(), name="rl-infer-flush"
            )
        return await fut

    async def _flush_after(self) -> None:
        import asyncio

        await asyncio.sleep(self._window)
        self._flush()

    def _flush(self) -> None:
        pending, self._pending = self._pending, []
        if not pending:
            return
        obs = np.concatenate([o for o, _ in pending], axis=0)
        n = len(obs)
        bucket = pow2_bucket(n)
        padded = np.zeros((bucket,) + obs.shape[1:], np.float32)
        padded[:n] = obs
        acts = np.asarray(self._greedy(self._params, padded))[:n]  # raylint: disable=RL101 -- the tier's intended sync: one batched readback feeding every coalesced caller
        self.stats["batches"] += 1
        self.stats["rows"] += n
        self.stats["max_batch_rows"] = max(self.stats["max_batch_rows"], n)
        if _metrics.metrics_enabled():
            _INFER_BATCH.observe(float(n))
        off = 0
        for o, fut in pending:
            if not fut.done():
                fut.set_result(acts[off : off + len(o)])
            off += len(o)

    def get_stats(self) -> dict:
        return dict(self.stats)

    def ping(self) -> bool:
        return True


# -- acting plane -------------------------------------------------------------


class PodracerEnvRunner(DQNEnvRunner):
    """DQN's epsilon-greedy collector with the podracer planes bolted on:
    greedy actions can route through an inference-tier replica, and one
    :meth:`podracer_rollout` call samples a fragment, stages it on the
    fabric, and pushes the descriptor into the bounded trajectory queue.
    Without :meth:`use_inference` it behaves exactly like DQNEnvRunner
    (the lockstep / kill-switch arm)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._infer = None
        self._armed_uids: collections.deque = collections.deque()

    def use_inference(self, replica) -> bool:
        self._infer = replica
        return True

    def greedy_actions(self, obs_in: np.ndarray) -> np.ndarray:
        if self._infer is None:
            return super().greedy_actions(obs_in)
        import ray_tpu

        return np.asarray(
            ray_tpu.get(self._infer.infer.remote(obs_in), timeout=60)
        )

    def podracer_rollout(
        self,
        queue_actor,
        epsilon: float,
        put_timeout_s: float = 10.0,
        hygiene_depth: int = 8,
    ) -> dict:
        """Sample ONE fragment into the trajectory queue. A full queue is
        the backpressure: the bounded put blocks (up to the timeout),
        which blocks this actor call, which stalls the supervisor's next
        dispatch. A timed-out put drops the fragment (off-policy replay
        tolerates gaps) rather than wedging the plane."""
        import ray_tpu

        self.set_epsilon(epsilon)
        batch = self.sample()
        entry, uid = stage_fragment(batch)
        self._armed_uids.append(uid)
        dropped = 0
        ok = ray_tpu.get(
            queue_actor.put.remote(entry, put_timeout_s),
            timeout=put_timeout_s + 30.0,
        )
        from ray_tpu.experimental import transfer as xfer

        if not ok:
            dropped = 1
            xfer.fabric().release_uuid(self._armed_uids.pop())
        # Producer-side staging hygiene: entries this many pushes old
        # have either been pulled (serve-once) or their consumer is
        # gone. The bound must exceed the trajectory queue depth (the
        # driver passes depth+1): a shallower bound releases entries
        # that are still sitting unpulled in the queue.
        while len(self._armed_uids) > max(1, hygiene_depth):
            xfer.fabric().release_uuid(self._armed_uids.popleft())
        return {
            "steps": len(batch),
            "dropped": dropped,
            "version": self._weights_version,
        }


# -- weight-sync plane --------------------------------------------------------


class WeightPublisher:
    """Versioned learner→actor weight publication over the transfer
    fabric. ``publish()`` bumps the version and ravels the params ONCE
    (``descriptor()`` arms the cached flat vector per consumer — N
    consumers cost N arms, not N full-model ravels); ``descriptor()``
    arms ONE serve-once flat-params entry (per consumer per version —
    the XLA engine serves an entry once). Entries ``staleness_steps + 1`` publishes old are released:
    the gate lets a consumer trail by ``staleness_steps`` versions, so
    applies for anything newer may still legitimately be in flight."""

    def __init__(self, learner_group, staleness_steps: int = 1):
        self._lg = learner_group
        self.version = 0
        self._horizon = max(1, int(staleness_steps)) + 1
        self._flat = None
        self._armed: collections.deque = collections.deque()
        self._lag_samples: list = []

    def publish(self) -> int:
        self.version += 1
        self._flat = self._lg.flat_weights()
        self._release_stale()
        return self.version

    def descriptor(self) -> dict:
        from ray_tpu.experimental import transfer as xfer

        if self._flat is None:
            self._flat = self._lg.flat_weights()
        desc = xfer.fabric().arm_group([self._flat])
        self._armed.append((self.version, desc["uuid"]))
        return desc

    def _release_stale(self) -> None:
        from ray_tpu.experimental import transfer as xfer

        while (
            self._armed
            and self._armed[0][0] <= self.version - self._horizon
        ):
            xfer.fabric().release_uuid(self._armed.popleft()[1])

    def reset_lag_window(self) -> None:
        """Start a fresh lag-percentile window (one per ``run()`` call:
        the samples of a previous decoupled run must not leak into this
        run's p99)."""
        self._lag_samples = []

    def note_applied(self, applied_versions) -> int:
        """Record the lag of the slowest consumer after a sync round."""
        lag = (
            self.version - min(applied_versions) if applied_versions else 0
        )
        self._lag_samples.append(lag)
        if _metrics.metrics_enabled():
            _WEIGHT_LAG.set(float(lag))
        return lag

    def lag_p99(self) -> float:
        if not self._lag_samples:
            return 0.0
        return float(np.percentile(np.asarray(self._lag_samples), 99))

    def close(self) -> None:
        from ray_tpu.experimental import transfer as xfer

        while self._armed:
            xfer.fabric().release_uuid(self._armed.popleft()[1])


# -- the driver ---------------------------------------------------------------


@dataclasses.dataclass
class PodracerConfig(DQNConfig):
    """DQN + the podracer plane knobs. ``podracer_staleness_steps=0`` is
    the lockstep (parity) arm; >= 1 decouples acting from learning with
    inference replicas at most that many published versions stale."""

    podracer_staleness_steps: int = 1
    num_inference_replicas: int = 1
    inference_batch_window_s: float = 0.002
    inference_max_batch: int = 64
    trajectory_queue_depth: int = 8
    # 0 -> replay_buffer_capacity. Any positive capacity works: the
    # device ring scatters through per-row modulo indices, so fragments
    # wrap across the ring edge without a host-side split.
    decoupled_replay_capacity: int = 0

    @property
    def algo_class(self) -> type:
        return PodracerDQN


class PodracerDQN(DQN):
    """DQN across the five podracer planes.

    ``train()`` is the lockstep iteration — byte-for-byte the single-loop
    DQN schedule (the parity arm), with the weight sync riding the
    fabric when the plane is enabled. ``run(target_env_steps)`` is the
    decoupled driver: sampler threads keep every runner rolling into the
    trajectory queue while the learner thread consumes device-resident
    fragments, updates, and publishes versioned weights under the
    staleness bound.
    """

    env_runner_cls = PodracerEnvRunner

    def __init__(self, config: PodracerConfig):
        super().__init__(config)
        self._publisher = WeightPublisher(
            self.learner_group,
            staleness_steps=config.podracer_staleness_steps,
        )
        self._last_learner_stats: dict = {}
        # Decoupled-plane state persists across run() calls: replica
        # actors and the queue actor are real processes (~seconds to
        # spawn + import jax), and the device replay ring must not
        # refill to learning_starts every call. Built lazily by the
        # first decoupled run, torn down in stop().
        self._replicas: list | None = None
        self._queue = None
        self._dreplay = None

    # -- weight sync ----------------------------------------------------------

    def _sync_weights(self) -> None:
        pub = getattr(self, "_publisher", None)
        if pub is None or not podracer_enabled():
            # Initial sync (publisher not built yet) or kill switch: the
            # direct actor-call path — value-identical either way.
            return super()._sync_weights()
        import ray_tpu

        version = pub.publish()
        applied = ray_tpu.get(
            [
                r.apply_weights.remote(version, pub.descriptor())
                for r in self.env_runners
            ]
        )
        pub.note_applied(applied)

    # -- decoupled driver -----------------------------------------------------

    def run(
        self,
        target_env_steps: int,
        time_budget_s: float | None = None,
    ) -> dict:
        """Run until ``target_env_steps`` fresh env steps land (or the
        budget expires). Decoupled when the plane is enabled and
        staleness >= 1; otherwise loops the lockstep iteration — the
        kill-switch A/B arm."""
        c = self.config
        if not podracer_enabled() or c.podracer_staleness_steps <= 0:
            return self._run_lockstep(target_env_steps, time_budget_s)
        return self._run_decoupled(target_env_steps, time_budget_s)

    def _run_lockstep(self, target: int, budget_s: float | None) -> dict:
        # Fresh lag window per run: without this, a lockstep run after a
        # decoupled one reports the PREVIOUS run's lag samples as its
        # p99 (the documented lockstep answer is 0).
        self._publisher.reset_lag_window()
        t0 = time.perf_counter()
        start = self._total_env_steps
        updates = 0
        while self._total_env_steps - start < target:
            if budget_s and time.perf_counter() - t0 > budget_s:
                break
            res = self.train()
            if res.get("learner"):
                # One grad step per sampled train batch (num_sgd_epochs=1,
                # minibatch_size=train_batch_size — the DQN contract).
                updates += self.config.num_train_batches_per_iteration
        return {
            "mode": "lockstep",
            "env_steps": self._total_env_steps - start,
            "grad_updates": updates,
            "weight_lag_p99": (
                self._publisher.lag_p99() if podracer_enabled() else 0.0
            ),
            "restarts": 0,
            "queue_drops": 0,
            "pull_failures": 0,
            "elapsed_s": round(time.perf_counter() - t0, 3),
        }

    def _respawn_runner(self, slot: int, replica):
        """Supervisor restart of a dead rollout actor: fresh actor, same
        seed/worker_index, current learner weights, same inference
        replica."""
        import ray_tpu

        maker = _env_maker(self.config.env)
        runner_opts = self.config.env_runner_resources or {"num_cpus": 1}
        r = (
            ray_tpu.remote(self.env_runner_cls)
            .options(**runner_opts)
            .remote(
                maker,
                self.module,
                **self.env_runner_kwargs(self.config, slot),
            )
        )
        ray_tpu.get(
            r.set_weights.remote(self.learner_group.get_weights()),
            timeout=120,
        )
        if replica is not None:
            ray_tpu.get(r.use_inference.remote(replica), timeout=60)
        self.env_runners[slot] = r
        return r

    def _respawn_replica(self, idx: int):
        """Supervisor restart of a dead inference replica: fresh actor
        seeded with the CURRENT learner params, swapped into the shared
        replica list in place — samplers attach respawned runners to
        ``replicas[slot % n_rep]`` at respawn time, so they pick the new
        replica up on their next restart cycle."""
        import ray_tpu

        c = self.config
        try:
            ray_tpu.kill(self._replicas[idx])
        except Exception:  # raylint: disable=RL006 -- the replica being respawned is already dead
            pass
        r = (
            ray_tpu.remote(InferenceServer)
            .options(num_cpus=0, max_concurrency=64)
            .remote(
                self.module,
                c.inference_batch_window_s,
                c.inference_max_batch,
            )
        )
        ray_tpu.get(
            r.set_weights.remote(self.learner_group.get_weights()),
            timeout=120,
        )
        self._replicas[idx] = r
        return r

    def _run_decoupled(self, target: int, budget_s: float | None) -> dict:
        import ray_tpu
        from ray_tpu.rllib.replay_buffer import DeviceReplay
        from ray_tpu.util.queue import Queue

        c = self.config
        pub = self._publisher
        pub.reset_lag_window()
        n_rep = max(1, c.num_inference_replicas)
        if self._replicas is None:
            self._replicas = [
                ray_tpu.remote(InferenceServer)
                .options(num_cpus=0, max_concurrency=64)
                .remote(
                    self.module,
                    c.inference_batch_window_s,
                    c.inference_max_batch,
                )
                for _ in range(n_rep)
            ]
        replicas = self._replicas
        init_w = self.learner_group.get_weights()
        ray_tpu.get(
            [r.set_weights.remote(init_w) for r in replicas], timeout=120
        )
        ray_tpu.get(
            [
                er.use_inference.remote(replicas[i % n_rep])
                for i, er in enumerate(self.env_runners)
            ],
            timeout=120,
        )
        if self._queue is None:
            self._queue = Queue(maxsize=c.trajectory_queue_depth)
        queue = self._queue
        stop = threading.Event()
        lock = threading.Lock()
        state = {
            "steps": 0,
            "updates": 0,
            "restarts": 0,
            "replica_restarts": 0,
            "drops": 0,
            "pull_failures": 0,
            "errors": [],
            # Per-phase learner-loop seconds (drain the queue / device
            # updates / publish+staleness gate): where a slow learner
            # plane actually spends its time.
            "learner_phase_s": {
                "drain": 0.0,
                "pull": 0.0,
                "update": 0.0,
                "sync": 0.0,
            },
            "pulled": 0,
            "rollout_s": 0.0,
            "rollouts": 0,
        }
        t0 = time.perf_counter()

        def done() -> bool:
            with lock:
                if state["steps"] >= target:
                    return True
            return bool(budget_s) and time.perf_counter() - t0 > budget_s

        def sampler(slot: int) -> None:
            while not stop.is_set() and not done():
                with lock:
                    total = self._total_env_steps
                # Same anneal as the lockstep arm, driven by shared steps.
                frac = min(
                    1.0, total / max(1, c.epsilon_anneal_steps)
                )
                eps = c.epsilon_initial + frac * (
                    c.epsilon_final - c.epsilon_initial
                )
                runner = self.env_runners[slot]
                t_roll = time.perf_counter()
                try:
                    out = ray_tpu.get(
                        runner.podracer_rollout.remote(
                            queue._actor,
                            eps,
                            10.0,
                            # Hygiene bound > queue depth: an entry may
                            # legitimately sit unpulled for depth pushes
                            # (plus one in-flight pull).
                            max(8, c.trajectory_queue_depth + 1),
                        ),
                        timeout=120,
                    )
                except Exception:  # raylint: disable=RL006 -- supervisor contract: ANY runner failure (chaos kill included) is restart-and-continue
                    if stop.is_set():
                        break
                    with lock:
                        state["restarts"] += 1
                    try:
                        self._respawn_runner(
                            slot, replicas[slot % n_rep]
                        )
                    except Exception:  # raylint: disable=RL006 -- respawn under teardown races actor cleanup; the loop re-checks stop
                        if stop.is_set():
                            break
                    continue
                with lock:
                    state["steps"] += out["steps"]
                    state["drops"] += out.get("dropped", 0)
                    state["rollout_s"] += time.perf_counter() - t_roll
                    state["rollouts"] += 1
                    self._total_env_steps += out["steps"]

        def learner() -> None:
            # A dead learner plane must surface in the run result (and
            # stop the run), not silently report 0 grad updates while the
            # acting plane spins to the step target.
            try:
                _learner_loop()
            except Exception as e:  # raylint: disable=RL006 -- plane-crash surfacing: the error lands in the result and ends the run
                import traceback

                with lock:
                    state["errors"].append(
                        f"learner: {type(e).__name__}: {e}\n"
                        + traceback.format_exc(limit=8)
                    )
                stop.set()

        def _learner_loop() -> None:
            if self._dreplay is None:
                self._dreplay = DeviceReplay(
                    c.decoupled_replay_capacity
                    or c.replay_buffer_capacity,
                    seed=c.seed,
                )
            dreplay = self._dreplay
            k = c.num_train_batches_per_iteration
            B = c.train_batch_size
            pending: list = []  # (replica_idx, ref, version)
            # Fresh replicas carry the CURRENT learner params (the
            # set_weights above), so they start at the current version —
            # not 0, or a re-run()'s gate would see a phantom lag of
            # everything published before this run.
            applied = [pub.version] * n_rep
            qactor = queue._actor
            phase_s = state["learner_phase_s"]
            def take_one(entry) -> None:
                t_pull = time.perf_counter()
                cols = load_fragment(entry)
                phase_s["pull"] += time.perf_counter() - t_pull
                with lock:
                    state["pulled"] += 1
                if cols is None:
                    with lock:
                        state["pull_failures"] += 1
                    return
                # Bucket-padded on the wire; entry["steps"] = valid rows.
                dreplay.add(cols, rows=entry["steps"])

            while not stop.is_set():
                t_mark = time.perf_counter()
                # Gate on LIFETIME rows, not ring size (the dqn.py
                # train() contract): a ring smaller than learning_starts
                # caps size below the threshold and must not disable
                # training forever.
                if dreplay.added() < max(c.learning_starts, B):
                    # Starved (cold ring): BLOCK on the queue actor — one
                    # RPC per fragment, not a get_nowait spin that floods
                    # the driver endpoint loop the samplers submit
                    # through.
                    ok, entry = ray_tpu.get(
                        qactor.get.remote(0.25), timeout=30
                    )
                    if ok:
                        take_one(entry)
                    phase_s["drain"] += time.perf_counter() - t_mark
                    continue
                # Warm: opportunistic non-blocking drain, a few per
                # round, between update bursts.
                drained = 0
                while drained < 4:
                    ok, entry = ray_tpu.get(qactor.get_nowait.remote())
                    if not ok:
                        break
                    drained += 1
                    take_one(entry)
                phase_s["drain"] += time.perf_counter() - t_mark
                t_mark = time.perf_counter()
                stats = None
                for _ in range(k):
                    stats = self.learner_group.update_device(
                        dreplay.sample(B)
                    )
                phase_s["update"] += time.perf_counter() - t_mark
                with lock:
                    state["updates"] += k
                if stats is not None:
                    # ONE host readback per learner round, off the
                    # per-minibatch path (round-13 cadence).
                    self._last_learner_stats = {
                        kk: float(v) for kk, v in stats.items()
                    }
                t_mark = time.perf_counter()
                version = pub.publish()
                for i, r in enumerate(replicas):
                    pending.append(
                        (
                            i,
                            r.apply_weights.remote(
                                version, pub.descriptor()
                            ),
                            version,
                        )
                    )
                # Staleness gate: do not start the next round while the
                # slowest replica trails by more than the bound.
                while not stop.is_set():
                    still = []
                    for i, ref, v in pending:
                        ready, _ = ray_tpu.wait(
                            [ref], num_returns=1, timeout=0
                        )
                        if ready:
                            try:
                                applied[i] = max(
                                    applied[i], ray_tpu.get(ref)
                                )
                            except Exception:  # raylint: disable=RL006 -- apply failure = dead replica (a weightsync sever is absorbed replica-side); supervisor respawn below
                                # A dead replica never advances its
                                # applied version: without a respawn the
                                # gate spins forever while the sampler
                                # keeps reattaching restarted runners to
                                # the corpse.
                                with lock:
                                    state["replica_restarts"] += 1
                                try:
                                    self._respawn_replica(i)
                                    # The fresh replica was seeded with
                                    # the CURRENT learner params.
                                    applied[i] = pub.version
                                except Exception:  # raylint: disable=RL006 -- respawn retries on the next failed apply; teardown races actor cleanup
                                    pass
                        else:
                            still.append((i, ref, v))
                    pending = still
                    if (
                        pub.version - min(applied)
                        <= c.podracer_staleness_steps
                    ):
                        break
                    if stop.wait(0.002):
                        break
                # ONE lag sample per sync round — not one per 2 ms spin
                # iteration, which biases the p99 toward over-bound
                # samples recorded while waiting and grows the window
                # unboundedly on a slow round.
                pub.note_applied(applied)
                phase_s["sync"] += time.perf_counter() - t_mark

        samplers = [
            threading.Thread(
                target=sampler, args=(i,), daemon=True,
                name=f"podracer-sampler-{i}",
            )
            for i in range(len(self.env_runners))
        ]
        learner_t = threading.Thread(
            target=learner, daemon=True, name="podracer-learner"
        )
        for th in samplers:
            th.start()
        learner_t.start()
        try:
            while not done() and not stop.is_set():
                time.sleep(0.02)
        finally:
            stop.set()
            for th in samplers:
                th.join(timeout=60)
            learner_t.join(timeout=60)
        # The ring is the learner thread's (its scatter donates the
        # buffers): only a learner that has exited hands it over.
        ring_free = not learner_t.is_alive()
        elapsed = time.perf_counter() - t0
        # Drain what the learner left behind so nothing stays armed and
        # the NEXT run (or a train() call) starts from an empty queue —
        # "never wedges". Drained fragments still land in the ring:
        # off-policy replay keeps them.
        leftover = 0
        while True:
            ok, entry = ray_tpu.get(queue._actor.get_nowait.remote())
            if not ok:
                break
            leftover += 1
            cols = load_fragment(entry)
            if cols is not None and self._dreplay is not None and ring_free:
                self._dreplay.add(cols, rows=entry["steps"])
        infer_stats = {}
        try:
            per_rep = ray_tpu.get(
                [r.get_stats.remote() for r in replicas], timeout=30
            )
            infer_stats = {
                "requests": sum(s["requests"] for s in per_rep),
                "batches": sum(s["batches"] for s in per_rep),
                "rows": sum(s["rows"] for s in per_rep),
                "max_batch_rows": max(
                    s["max_batch_rows"] for s in per_rep
                ),
            }
        except Exception:  # raylint: disable=RL006 -- stats fetch from a dead replica is best-effort
            pass
        # Detach the inference tier (train()/lockstep must run local
        # greedy), but leave replicas + queue alive for the next run()
        # — they are processes, respawning them per call costs seconds.
        for er in self.env_runners:
            try:
                ray_tpu.get(er.use_inference.remote(None), timeout=30)
            except Exception:  # raylint: disable=RL006 -- runner may be mid-restart at teardown; lockstep reattach is best-effort
                pass
        pub.close()
        with lock:
            summary = dict(state)
        return {
            "mode": "decoupled",
            "env_steps": summary["steps"],
            "grad_updates": summary["updates"],
            "weight_lag_p99": pub.lag_p99(),
            "weight_version": pub.version,
            "restarts": summary["restarts"],
            "replica_restarts": summary["replica_restarts"],
            "queue_drops": summary["drops"],
            "pull_failures": summary["pull_failures"],
            "queue_leftover": leftover,
            "errors": summary["errors"],
            "learner_phase_s": {
                kk: round(v, 3)
                for kk, v in summary["learner_phase_s"].items()
            },
            "fragments_pulled": summary["pulled"],
            "rollout_mean_s": round(
                summary["rollout_s"] / max(1, summary["rollouts"]), 4
            ),
            "inference": infer_stats,
            "learner": dict(self._last_learner_stats),
            "elapsed_s": round(elapsed, 3),
        }

    def stop(self) -> None:
        import ray_tpu

        for r in self._replicas or ():
            try:
                ray_tpu.kill(r)
            except Exception:  # raylint: disable=RL006 -- teardown kill; replica already dead
                pass
        self._replicas = None
        if self._queue is not None:
            self._queue.shutdown()
            self._queue = None
        self._publisher.close()
        super().stop()
