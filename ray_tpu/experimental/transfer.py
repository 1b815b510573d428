"""Device-to-device tensor transfer between separately initialized JAX
programs (SPMD "worlds") — no host staging, no pickle of device buffers.

Reference parity: python/ray/experimental/channel/torch_tensor_accelerator_channel.py:49
(NCCL P2P between compiled programs) and
python/ray/experimental/gpu_object_manager/nixl_tensor_transport.py (RDMA-style
point-to-point tensor pull). TPU-native redesign: instead of a NCCL/NIXL
communicator pair, each process runs one `jax.experimental.transfer` server —
XLA's cross-host transfer engine (DCN-backed on real TPU pods, socket-backed
elsewhere). The consumer *pulls*: buffers move directly between XLA device
runtimes; the control plane only carries a tiny "arm" RPC.

Protocol (one producer process -> one consumer process):

1. Consumer picks a shard *decomposition* — per-dimension partition counts,
   e.g. ``(1, 4)`` = dim1 split 4 ways — typically derived from the sharding
   it wants the array to land in (:func:`decomposition_of`).
2. Consumer sends ``worker.rdt_arm {oid, partitions}`` to the owner.
3. Owner re-lays-out the array to that decomposition *on its own devices*
   (``jax.device_put`` — an on-device XLA reshard, ICI-local), schedules it
   with ``server.await_pull(uuid, ...)``, and replies
   ``{uuid, address, shape, dtype, partitions}``.
4. Consumer builds the byte-identical decomposition over *its* devices and
   ``connection.pull``s: each shard travels device-to-device through the
   transfer engine. A final local ``device_put`` moves the result into the
   consumer's target sharding if it differs.

The fabric requires the shard layouts on both ends to match byte-for-byte
(the engine moves shards, it does not reshard) — that is why the producer
re-lays-out first. Arrays must be fully addressable in the owner process
(one-controller worlds). Multi-controller worlds — where each process
owns only its addressable shards — use the per-process catalog/arm/pull
protocol in :mod:`ray_tpu.experimental.multiworld` on top of this same
fabric.
"""

from __future__ import annotations

import math
import threading
import uuid as _uuid
from typing import Any, Optional, Sequence

_AXIS_PREFIX = "_xfer"


class _Fabric:
    """Per-process transfer server + connection cache (lazily started)."""

    # Bound on retained armed entries: a consumer that pulls but whose
    # completion notify is lost (or that dies mid-pull) must not pin our
    # bookkeeping forever. Only entries OLDER than ARMED_TTL_S are evicted
    # (with a budget refund): a younger entry's pull may still be in
    # flight — the transfer server cannot unschedule an await_pull, so
    # evicting it would risk serving the pull AND refunding the budget
    # (a double fetch). After the TTL (the consumer's arm RPC timeout) the
    # pull has certainly failed or timed out.
    ARMED_CAP = 16
    ARMED_TTL_S = 120.0
    # The engine's pull returns at once with arrays that fill in later, and
    # a producer that died never fails them: they simply never become
    # ready, and whatever consumes one blocks for good. Every caller's
    # fallback (drop the fragment, prefill locally, restore a checkpoint)
    # hangs off an exception, so a pull that has not landed by this
    # deadline raises.
    PULL_TIMEOUT_S = 20.0

    def __init__(self):
        import collections

        self._lock = threading.Lock()
        self._server = None
        self._conns: dict[str, Any] = {}
        # Keep armed arrays alive until pulled-or-freed:
        # uuid -> (oid, array, armed_at_monotonic).
        self._armed: "collections.OrderedDict[int, tuple]" = (
            collections.OrderedDict()
        )
        from ray_tpu.core.config import GLOBAL_CONFIG

        self._armed_cap = int(GLOBAL_CONFIG.xfer_armed_cap)
        self._stats = {"arms": 0, "pulls": 0, "fallbacks": 0}

    # -- server ----------------------------------------------------------------

    def _ensure_server(self):
        if self._server is not None:
            return self._server
        with self._lock:
            if self._server is None:
                import jax
                from jax.experimental import transfer

                from ray_tpu.util.net import local_ip

                ip = local_ip()
                client = jax.local_devices()[0].client
                # Explicit socket transport addresses: the default local
                # bulk transport only pairs processes created by one
                # runtime and aborts across unrelated ones.
                self._server = transfer.start_transfer_server(
                    client, f"{ip}:0", [f"{ip}:0"]
                )
        return self._server

    def address(self) -> str:
        return self._ensure_server().address()

    def _connect(self, address: str):
        server = self._ensure_server()
        with self._lock:
            conn = self._conns.get(address)
            if conn is None:
                conn = server.connect(address)
                self._conns[address] = conn
            return conn

    # -- producer side ---------------------------------------------------------

    def arm(self, oid: str, array, partitions: Sequence[int]) -> dict:
        """Re-layout ``array`` to ``partitions`` on local devices and schedule
        it for one remote pull. Returns the pull descriptor."""
        import jax

        partitions = _normalize_partitions(array.shape, partitions)
        if math.prod(partitions) > len(jax.local_devices()):
            # Consumer asked for more shards than this world has devices:
            # stage single-device; the consumer re-lays-out after the pull.
            partitions = (1,) * len(array.shape)
        sharding = _decomposed_sharding(partitions)
        staged = jax.device_put(array, sharding)
        uid = _uuid.uuid4().int >> 65  # 63-bit
        self._ensure_server().await_pull(uid, [staged])
        self._remember_armed(uid, oid, staged)
        return {
            "uuid": uid,
            "address": self.address(),
            "shape": tuple(array.shape),
            "dtype": str(array.dtype),
            "partitions": tuple(partitions),
        }

    def _remember_armed(self, uid: int, oid, staged) -> None:
        """Record one armed entry and run the cap/TTL eviction sweep."""
        import time

        evicted = []
        now = time.monotonic()
        with self._lock:
            self._armed[uid] = (oid, staged, now)
            while len(self._armed) > self._armed_cap:
                old_uid, entry = next(iter(self._armed.items()))
                if now - entry[2] < self.ARMED_TTL_S:
                    break  # young entries: pull may still be in flight
                del self._armed[old_uid]
                evicted.append(entry)
            self._stats["arms"] += 1
        # A TTL-evicted entry's fetch budget was consumed at arm time and
        # its pull can no longer land; refund it so the object is not lost
        # (every other failure path refunds the same way). oid None =
        # channel-owned arm (DeviceChannel / trajectory-queue group): no
        # store entry to refund.
        if evicted:
            from ray_tpu.experimental.device_objects import store

            for ev_oid, ev_staged, _t in evicted:
                if ev_oid is not None:
                    store().restore_arm(ev_oid, ev_staged)

    def arm_group(self, arrays: Sequence) -> dict:
        """Stage SEVERAL arrays under ONE uid for one remote pull — the
        trajectory-plane unit (a rollout fragment's columns travel
        together: one arm RPC worth of descriptor, one pull). Single-device
        layout on both
        ends; a consumer that wants a sharded landing re-lays-out after
        the pull, exactly like an over-decomposed :meth:`arm`."""
        import jax
        import jax.numpy as jnp

        staged = [jax.device_put(jnp.asarray(a)) for a in arrays]
        uid = _uuid.uuid4().int >> 65  # 63-bit
        self._ensure_server().await_pull(uid, staged)
        self._remember_armed(uid, None, staged)
        return {
            "uuid": uid,
            "address": self.address(),
            "specs": [
                {"shape": tuple(a.shape), "dtype": str(a.dtype)}
                for a in staged
            ],
            "group": True,
        }

    def pull_group(self, desc: dict) -> list:
        """Pull an :meth:`arm_group` entry: every member array lands on
        local devices (single-device layout, matching the producer's)."""
        import jax
        import jax.numpy as jnp

        specs = [
            jax.ShapeDtypeStruct(
                tuple(s["shape"]),
                jnp.dtype(s["dtype"]),
                sharding=_decomposed_sharding((1,) * len(s["shape"])),
            )
            for s in desc["specs"]
        ]
        conn = self._connect(desc["address"])
        out = self._landed(conn.pull(desc["uuid"], specs), desc["address"])
        with self._lock:
            self._stats["pulls"] += 1
        return out

    def _landed(self, arrays: list, address: str) -> list:
        """``arrays`` once the transfer engine has filled them all in;
        TimeoutError if that takes longer than PULL_TIMEOUT_S."""
        import time

        deadline = time.monotonic() + self.PULL_TIMEOUT_S
        pause = 0.0005
        while not all(a.is_ready() for a in arrays):
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"pull from {address} did not land within "
                    f"{self.PULL_TIMEOUT_S:.0f}s (producer gone?)"
                )
            time.sleep(pause)
            pause = min(pause * 2, 0.05)
        return arrays

    def release_armed(self, oid: str) -> None:
        """Drop armed entries for an oid (object freed before any pull)."""
        with self._lock:
            uids = [
                u for u, entry in self._armed.items() if entry[0] == oid
            ]
            for uid in uids:
                del self._armed[uid]

    def release_uuid(self, uid: int):
        """Drop one armed entry (pull completed, or consumer unarms after a
        failed pull). Returns (oid, staged_array) or None."""
        with self._lock:
            entry = self._armed.pop(int(uid), None)
        return entry

    # -- consumer side ---------------------------------------------------------

    def pull(self, desc: dict, target_sharding=None):
        """Pull an armed array from ``desc`` into local devices; optionally
        re-layout into ``target_sharding`` afterwards (on-device)."""
        import jax
        import jax.numpy as jnp

        dtype = jnp.dtype(desc["dtype"])
        shape = tuple(desc["shape"])
        sharding = _decomposed_sharding(desc["partitions"])
        spec = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        conn = self._connect(desc["address"])
        [out] = self._landed(
            conn.pull(desc["uuid"], [spec]), desc["address"]
        )
        with self._lock:
            self._stats["pulls"] += 1
        if target_sharding is not None and out.sharding != target_sharding:
            out = jax.device_put(out, target_sharding)
        return out

    def count_fallback(self) -> None:
        with self._lock:
            self._stats["fallbacks"] += 1

    def stats(self) -> dict:
        with self._lock:
            return dict(self._stats, armed=len(self._armed))


_fabric: Optional[_Fabric] = None
_fabric_lock = threading.Lock()


def fabric() -> _Fabric:
    global _fabric
    if _fabric is None:
        with _fabric_lock:
            if _fabric is None:
                _fabric = _Fabric()
    return _fabric


def transfer_stats() -> dict:
    """Counters for tests/observability ({arms, pulls, fallbacks, armed})."""
    return fabric().stats() if _fabric is not None else {
        "arms": 0, "pulls": 0, "fallbacks": 0, "armed": 0,
    }


# -- decomposition helpers -----------------------------------------------------


def _normalize_partitions(shape, partitions) -> tuple[int, ...]:
    partitions = tuple(int(p) for p in partitions)
    if len(partitions) != len(shape):
        raise ValueError(
            f"partitions {partitions} rank != array rank {len(shape)}"
        )
    if any(p < 1 for p in partitions):
        raise ValueError(f"partitions must be >= 1, got {partitions}")
    return partitions


def _decomposed_sharding(partitions: Sequence[int]):
    """A NamedSharding over this process's local devices realizing the given
    per-dim partition counts, with deterministic (row-major) shard order —
    identical construction on both ends makes shard lists line up 1:1."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    partitions = tuple(int(p) for p in partitions)
    devices = jax.local_devices()
    if not partitions:  # rank-0 array: single-device on both ends
        return jax.sharding.SingleDeviceSharding(devices[0])
    k = math.prod(partitions)
    if k > len(devices):
        raise ValueError(
            f"decomposition {partitions} needs {k} devices; this process "
            f"has {len(devices)}"
        )
    names = tuple(f"{_AXIS_PREFIX}{i}" for i in range(len(partitions)))
    mesh = Mesh(np.array(devices[:k]).reshape(partitions), names)
    return NamedSharding(mesh, P(*names))


def decomposition_of(sharding, shape) -> tuple[int, ...]:
    """Per-dimension partition counts of ``sharding`` applied to ``shape``
    (the decomposition a consumer asks the producer to stage)."""
    shard = sharding.shard_shape(tuple(shape))
    return tuple(
        -(-int(g) // int(s)) if s else 1 for g, s in zip(shape, shard)
    )


def max_local_decomposition(shape) -> tuple[int, ...]:
    """Largest power-of-two split of dim0 that fits this process's devices —
    a reasonable default when the consumer has no target sharding: spreads
    the pull across devices (parallel transfer streams) without exceeding
    either side's device count."""
    import jax

    n = len(jax.local_devices())
    if not shape:
        return ()
    split = 1
    while split * 2 <= n and shape[0] % (split * 2) == 0:
        split *= 2
    return (split,) + (1,) * (len(shape) - 1)
