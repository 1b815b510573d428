"""Device-resident objects: keep jax.Arrays on their device across actor
boundaries.

Reference parity: python/ray/experimental/gpu_object_manager/
(GPUObjectStore gpu_object_store.py, owner-side GPUObjectMeta, hidden
__ray_send__/__ray_recv__ transfer methods, NCCL/NIXL transports).
TPU-native redesign:

- The store is per-PROCESS (module global) and served by a core-worker RPC
  ("worker.rdt_fetch"), so any actor's arrays are fetchable without
  touching the user's class — the reference injects hidden methods instead.
- The default transfer is device -> host -> RPC -> device: on TPU, ad-hoc
  point-to-point between two arbitrary OS processes without a shared XLA
  runtime has no ICI path (device collectives belong to jitted SPMD
  programs over a mesh — that fast path is
  :mod:`ray_tpu.util.collective`'s XLA backend, used where both ends joined
  one multi-controller runtime).
- ``enable_device_objects()`` turns on transparent interception: actor
  task RETURN values keep their device arrays local (replaced by
  ``DeviceRef`` markers in the payload); consumers reassemble eagerly at
  deserialization, fetching from the owner.

Lifetime: owner-side entries are dropped on ``device_free``, when the
owning process exits, or — for intercepted returns — after
``default_fetches_before_free`` fetches (1 matches the common produce->
consume handoff; set 0 to keep until freed).
"""

from __future__ import annotations

import dataclasses
import threading
import uuid
from typing import Any, Optional

import numpy as np


@dataclasses.dataclass
class _Entry:
    array: Any
    fetches_left: int  # 0 = unlimited


class DeviceObjectStore:
    """Per-process store of device arrays (reference: GPUObjectStore)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._objects: dict[str, _Entry] = {}

    def put(self, oid: str, array, fetches_before_free: int = 0) -> None:
        with self._lock:
            self._objects[oid] = _Entry(array, fetches_before_free)

    def get_local(self, oid: str):
        with self._lock:
            entry = self._objects.get(oid)
        return None if entry is None else entry.array

    def fetch_host(self, oid: str) -> Optional[np.ndarray]:
        """Device -> host for shipping; applies the fetch budget."""
        array = self.take_for_arm(oid)
        return None if array is None else np.asarray(array)

    def take_for_arm(self, oid: str):
        """Like fetch_host but returns the DEVICE array for staging on the
        transfer fabric (applies the same fetch budget)."""
        with self._lock:
            entry = self._objects.get(oid)
            if entry is None:
                return None
            if entry.fetches_left > 0:
                entry.fetches_left -= 1
                if entry.fetches_left == 0:
                    del self._objects[oid]
            return entry.array

    def restore_arm(self, oid: str, array) -> None:
        """Undo a take_for_arm whose staging failed (budget refund)."""
        with self._lock:
            entry = self._objects.get(oid)
            if entry is None:
                self._objects[oid] = _Entry(array, 1)
            elif entry.fetches_left > 0:
                entry.fetches_left += 1

    def free(self, oid: str) -> bool:
        with self._lock:
            return self._objects.pop(oid, None) is not None

    def stats(self) -> dict:
        with self._lock:
            return {
                "num_objects": len(self._objects),
                "nbytes": sum(
                    getattr(e.array, "nbytes", 0)
                    for e in self._objects.values()
                ),
            }


_store = DeviceObjectStore()
# Per-PROCESS interception state (NOT thread-local: the user enables it in
# the executor thread, but actor-return serialization runs on the endpoint
# loop thread — a thread-local flag would silently never apply).
_intercept: dict = {"on": False, "fetches": 1}


def store() -> DeviceObjectStore:
    return _store


@dataclasses.dataclass(frozen=True)
class DeviceRef:
    """Picklable handle to a device array living in another process.

    ``owner_addr`` is the owning core worker's RPC address; fetching pulls
    the array to host there and re-device-puts locally.
    """

    oid: str
    owner_addr: tuple
    shape: tuple
    dtype: str

    def __reduce__(self):
        return (
            DeviceRef,
            (self.oid, self.owner_addr, self.shape, self.dtype),
        )


def _current_worker():
    from ray_tpu.core import api as core_api

    return core_api._require_worker(auto_init=False)


def device_put(value, *, fetches_before_free: int = 0) -> DeviceRef:
    """Register a (device) array in this process's store; returns a
    picklable DeviceRef to hand to other actors."""
    worker = _current_worker()
    oid = f"dev-{uuid.uuid4().hex[:16]}"
    _store.put(oid, value, fetches_before_free)
    return DeviceRef(
        oid=oid,
        owner_addr=tuple(worker.endpoint.address),
        shape=tuple(getattr(value, "shape", ())),
        dtype=str(getattr(value, "dtype", "")),
    )


def device_get(ref: DeviceRef, *, to_device: bool = True, sharding=None):
    """Resolve a DeviceRef: local hit returns the original array; otherwise
    transfer from the owner.

    The default path is device-to-device over the JAX transfer fabric
    (:mod:`ray_tpu.experimental.transfer`): the owner stages the array in a
    consumer-chosen shard decomposition and the buffers move directly
    between XLA runtimes — no host pickle. ``sharding`` (a local
    NamedSharding) selects where the result lands; without it the pull
    spreads dim0 across local devices. Host-staged RPC remains the fallback
    (non-array values, fabric-less platforms, RAY_TPU_RDT_FABRIC=0).
    """
    import os

    local = _store.get_local(ref.oid)
    if local is not None:
        return local
    worker = _current_worker()
    if worker.endpoint.on_loop():
        # Deserialization paths must never reach here (arg loads run in
        # the executor thread); blocking the endpoint loop on its own RPC
        # would deadlock it.
        raise RuntimeError(
            "device_get called on the endpoint event loop; fetch from the "
            "task/actor execution thread instead"
        )
    if (
        to_device
        and ref.dtype  # empty dtype = non-array value: host path directly
        and os.environ.get("RAY_TPU_RDT_FABRIC", "1") != "0"
    ):
        from ray_tpu.experimental import transfer as _xfer

        try:
            if sharding is not None:
                partitions = _xfer.decomposition_of(sharding, ref.shape)
            else:
                partitions = _xfer.max_local_decomposition(ref.shape)
            desc = worker.endpoint.call(
                tuple(ref.owner_addr),
                "worker.rdt_arm",
                {"oid": ref.oid, "partitions": tuple(partitions)},
                timeout=120,
            )
        except Exception:  # raylint: disable=RL006 -- owner predates rdt_arm or RPC failed: host path
            desc = None  # owner predates rdt_arm or RPC failed: host path
        if desc is not None and desc.get("gone"):
            raise KeyError(
                f"device object {ref.oid} is gone from its owner (freed or "
                f"fetch budget exhausted)"
            )
        if desc is None or desc.get("unsupported"):
            # Arm RPC failed or the owner can't serve this object over the
            # fabric: the host fetch below is a fallback and must count as
            # one — tests use transfer_stats()['fallbacks'] == 0 as proof
            # the fabric carried the data.
            _xfer.fabric().count_fallback()
        if desc is not None and not desc.get("unsupported"):
            try:
                out = _xfer.fabric().pull(desc, target_sharding=sharding)
            except Exception:
                # Refund the fetch budget the arm consumed (and drop the
                # staged copy) so the host fallback below still finds the
                # object — without this, a budget-1 ref would read as
                # "gone" even though the data sits armed at the owner.
                try:
                    worker.endpoint.call(
                        tuple(ref.owner_addr),
                        "worker.rdt_unarm",
                        {"uuid": desc["uuid"]},
                        timeout=30,
                    )
                except Exception:  # raylint: disable=RL006 -- rdt_fetch fallback notify; owner-side armed-cap eviction covers it
                    pass
                _xfer.fabric().count_fallback()
            else:
                # Ack so the owner drops its staged HBM copy now rather
                # than holding it until cap eviction.
                try:
                    worker.endpoint.notify_sync(
                        tuple(ref.owner_addr),
                        "worker.rdt_done",
                        {"uuid": desc["uuid"]},
                    )
                except Exception:  # raylint: disable=RL006 -- best-effort free of the armed staging entry; cap eviction covers it
                    pass
                return out
    host = worker.endpoint.call(
        tuple(ref.owner_addr),
        "worker.rdt_fetch",
        {"oid": ref.oid},
        timeout=120,
    )
    if host is None:
        raise KeyError(
            f"device object {ref.oid} is gone from its owner (freed or "
            f"fetch budget exhausted)"
        )
    if not to_device:
        return host
    import jax

    if sharding is not None:
        return jax.device_put(host, sharding)
    return jax.device_put(host)


def device_free(ref: DeviceRef) -> bool:
    """Drop the owner-side entry (local call or RPC)."""
    local = _store.free(ref.oid)
    if local:
        return True
    worker = _current_worker()
    try:
        return bool(
            worker.endpoint.call(
                tuple(ref.owner_addr),
                "worker.rdt_free",
                {"oid": ref.oid},
                timeout=30,
            )
        )
    except Exception:  # raylint: disable=RL006 -- fabric capability probe; False routes transfers through the host path
        return False


def device_store_stats() -> dict:
    return _store.stats()


# ---------------------------------------------------------------------------
# Transparent interception (reference: tensor_transport on @ray.remote)
# ---------------------------------------------------------------------------


def enable_device_objects(fetches_before_free: int = 1) -> None:
    """From now on IN THIS PROCESS, device arrays inside serialized values
    (actor returns, put()s) stay on-device here and travel as DeviceRefs;
    deserializing processes fetch them eagerly."""
    _intercept["fetches"] = fetches_before_free
    _intercept["on"] = True


def disable_device_objects() -> None:
    _intercept["on"] = False


def intercept_active() -> bool:
    return _intercept["on"]


def intercept_reduce(obj):
    """Called by the serializer for on-device jax arrays when interception
    is active: park the array locally, emit a fetch-on-load marker."""
    ref = device_put(obj, fetches_before_free=_intercept["fetches"])
    return (_load_device_ref, (ref,))


def _load_device_ref(ref: DeviceRef):
    return device_get(ref)
