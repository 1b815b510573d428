"""JAX backend — forms one multi-controller JAX runtime over the worker group.

Reference parity: python/ray/train/v2/jax/config.py (JaxConfig :23,
_JaxBackend :112 — worker 0's address becomes the coordinator, every worker
runs jax.distributed.initialize(coordinator, num_workers, index) :84;
multi-slice MegaScale env injection :126-151). Workers are already
rank-sorted by (slice, host) so process indices are stable across restarts
and the sequence axis lands on contiguous ICI neighbors.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import cloudpickle

import ray_tpu
from ray_tpu.train.backend import Backend, BackendConfig


@dataclass
class JaxConfig(BackendConfig):
    """distributed: run jax.distributed.initialize across the group (turn off
    for single-worker debug runs). platform: pin a jax platform in workers
    ("cpu" in tests, which never take a chip)."""

    distributed: bool = True
    platform: Optional[str] = None
    num_slices: int = 1

    def backend_cls(self):
        return _JaxBackend


def _jax_shutdown_worker():
    """Tear down a live jax.distributed runtime inside a surviving worker
    so the elastic re-formation can re-initialize at the new world size
    (jax refuses a second initialize() while the old one is up)."""
    import jax

    if jax.distributed.is_initialized():
        jax.distributed.shutdown()
    return True


def _jax_init_worker(
    platform: Optional[str],
    coordinator: Optional[str],
    num_processes: int,
    process_id: int,
    megascale_env: dict,
):
    """Runs inside each train worker BEFORE any other jax use."""
    import os

    os.environ.update(megascale_env)
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    if coordinator is not None and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    return True


class _JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxConfig) -> None:
        workers = worker_group.workers
        n = len(workers)
        coordinator = None
        if backend_config.distributed and n >= 1:
            head = workers[0]
            port = ray_tpu.get(head.actor.free_port.remote())
            ip = head.metadata.get("ip") or "127.0.0.1"
            coordinator = f"{ip}:{port}"
        payload = cloudpickle.dumps(_jax_init_worker)
        # Slice index = order of the worker's slice among the reserved
        # slices (rank order already groups workers by slice).
        slice_order: list[str] = []
        for info in workers:
            s = info.metadata.get("slice_name", "")
            if s not in slice_order:
                slice_order.append(s)
        refs = []
        for w in workers:
            megascale = {}
            if backend_config.num_slices > 1:
                from ray_tpu.util.tpu import get_tpu_coordinator_env_vars

                slice_id = slice_order.index(
                    w.metadata.get("slice_name", "")
                )
                megascale = get_tpu_coordinator_env_vars(
                    (coordinator or "127.0.0.1:0").split(":")[0],
                    backend_config.num_slices,
                    slice_id,
                )
            refs.append(
                w.actor.execute.remote(
                    payload,
                    backend_config.platform,
                    coordinator if backend_config.distributed else None,
                    n,
                    w.world_rank,
                    megascale,
                )
            )
        ray_tpu.get(refs, timeout=300)

    def on_reshape(self, worker_group, backend_config: JaxConfig) -> None:
        """Live re-init at the new world size: survivors shut their old
        jax.distributed runtime down (the old coordinator may be on a
        preempted node), then the start hook re-forms it with the new
        rank 0 as coordinator and the new process count."""
        if backend_config.distributed:
            payload = cloudpickle.dumps(_jax_shutdown_worker)
            ray_tpu.get(
                [
                    w.actor.execute.remote(payload)
                    for w in worker_group.workers
                ],
                timeout=120,
            )
        self.on_start(worker_group, backend_config)
