"""Multi-controller transfer fabric: per-process arm/pull of addressable
shards, so a K-process SPMD world hands a sharded array to an M-process
world with no host staging.

Reference parity: python/ray/experimental/gpu_object_manager/
gpu_object_store.py (the multi-worker RDT case NIXL handles for the
reference). The single-controller fabric (:mod:`.transfer`) stages the
WHOLE array in one process; in a multi-controller world no process can do
that — each process owns only its addressable shards. Protocol:

1. Every producer process publishes a **catalog** of its addressable
   shards (:func:`export_shards` — global index boxes + shapes, no
   device data moves).
2. Each consumer process computes which producer shards overlap any of
   its own target regions (:func:`plan_pulls`) and asks the owning
   producer processes to **arm** exactly those (:func:`arm_shards` —
   one ``await_pull`` per shard, served once).
3. The consumer pulls each armed shard device-to-device through the
   transfer engine, slices out the overlaps, and assembles its local
   shards with on-device ``dynamic_update_slice``
   (:func:`pull_and_assemble`) — finishing with
   ``jax.make_array_from_single_device_arrays`` over the target
   sharding. No buffer ever touches the host.

The RPC plumbing between worlds stays with the caller (Train workers are
actors; the catalogs/descriptors are tiny dicts) — these functions are
the device-path building blocks.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

from ray_tpu.experimental.transfer import fabric


def _normalize_box(index, shape) -> tuple:
    """Tuple of (start, stop) per dim from a shard's index (slices)."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = int(dim) if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def _overlap(a: tuple, b: tuple) -> Optional[tuple]:
    """Intersection box of two (start, stop) boxes, or None."""
    out = []
    for (a0, a1), (b0, b1) in zip(a, b):
        lo, hi = max(a0, b0), min(a1, b1)
        if lo >= hi:
            return None
        out.append((lo, hi))
    return tuple(out)


def export_shards(array) -> dict:
    """Catalog of THIS process's addressable shards — pure metadata."""
    import jax

    shards = []
    for pos, sh in enumerate(array.addressable_shards):
        shards.append(
            {
                "pos": pos,
                "box": _normalize_box(sh.index, array.shape),
                "shape": tuple(sh.data.shape),
            }
        )
    return {
        "process_index": jax.process_index(),
        "global_shape": tuple(array.shape),
        "dtype": str(array.dtype),
        "shards": shards,
    }


def arm_shards(array, positions: Sequence[int], *, oid: str | None = None) -> dict:
    """Arm this process's addressable shards at ``positions`` for ONE
    pull each. Returns {"address", "armed": {pos: uuid}}. Entries ride
    the fabric's armed table (TTL/cap evicted like single-world arms)."""
    import time
    import uuid as _uuid

    fab = fabric()
    server = fab._ensure_server()
    local = list(array.addressable_shards)
    armed = {}
    now = time.monotonic()
    for pos in positions:
        sh = local[int(pos)]
        uid = _uuid.uuid4().int >> 65
        server.await_pull(uid, [sh.data])
        with fab._lock:
            fab._armed[uid] = (oid, sh.data, now)
            fab._stats["arms"] += 1
        armed[int(pos)] = uid
    return {"address": fab.address(), "armed": armed}


def plan_pulls(catalogs: Sequence[dict], target_sharding, global_shape) -> dict:
    """{producer process_index: [pos, ...]} — the producer shards THIS
    consumer process needs (overlap with any of its addressable target
    regions)."""

    idx_map = target_sharding.addressable_devices_indices_map(
        tuple(global_shape)
    )
    regions = [
        _normalize_box(ix, global_shape) for ix in idx_map.values()
    ]
    plan: dict[int, list] = {}
    for cat in catalogs:
        poss = [
            s["pos"]
            for s in cat["shards"]
            if any(_overlap(r, tuple(map(tuple, s["box"]))) for r in regions)
        ]
        if poss:
            plan[cat["process_index"]] = poss
    return plan


def pull_and_assemble(
    catalogs: Sequence[dict],
    descriptors: Sequence[dict],
    target_sharding,
    *,
    global_shape: Optional[tuple] = None,
    dtype: Any = None,
) -> Any:
    """Pull this process's needed shards and build its part of the global
    array under ``target_sharding``.

    ``catalogs``/``descriptors`` line up 1:1 per producer process (the
    descriptor is ``arm_shards``'s return). Each needed shard is pulled
    ONCE per consumer process (first needing device), reused across local
    devices via on-device copies. Returns the global jax.Array."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    fab = fabric()
    global_shape = tuple(global_shape or catalogs[0]["global_shape"])
    dtype = jnp.dtype(dtype or catalogs[0]["dtype"])
    idx_map = target_sharding.addressable_devices_indices_map(global_shape)

    by_proc = {c["process_index"]: (c, d) for c, d in
               zip(catalogs, descriptors)}
    pulled: dict[tuple, Any] = {}  # (address, pos) -> pulled shard
    local_arrays = []
    for dev, region in idx_map.items():
        region_n = _normalize_box(region, global_shape)
        local_shape = tuple(hi - lo for lo, hi in region_n)
        buf = jax.device_put(jnp.zeros(local_shape, dtype), dev)
        for cat, desc in by_proc.values():
            for shard in cat["shards"]:
                box = tuple(map(tuple, shard["box"]))
                ov = _overlap(region_n, box)
                if ov is None:
                    continue
                key = (desc["address"], shard["pos"])
                arr = pulled.get(key)
                if arr is None:
                    uid = desc["armed"].get(shard["pos"]) or desc[
                        "armed"
                    ].get(str(shard["pos"]))
                    if uid is None:
                        raise KeyError(
                            f"producer {cat['process_index']} did not arm "
                            f"shard {shard['pos']} (re-run plan_pulls?)"
                        )
                    spec = jax.ShapeDtypeStruct(
                        tuple(shard["shape"]),
                        dtype,
                        sharding=SingleDeviceSharding(dev),
                    )
                    conn = fab._connect(desc["address"])
                    [arr] = fab._landed(
                        conn.pull(uid, [spec]), desc["address"]
                    )
                    with fab._lock:
                        fab._stats["pulls"] += 1
                    pulled[key] = arr
                piece = arr[
                    tuple(
                        slice(lo - b0, hi - b0)
                        for (lo, hi), (b0, _b1) in zip(ov, box)
                    )
                ]
                if piece.devices() != {dev}:
                    piece = jax.device_put(piece, dev)  # local D2D copy
                buf = jax.lax.dynamic_update_slice(
                    buf,
                    piece,
                    tuple(
                        lo - r0 for (lo, _hi), (r0, _r1) in zip(ov, region_n)
                    ),
                )
        local_arrays.append(buf)
    return jax.make_array_from_single_device_arrays(
        global_shape, target_sharding, local_arrays
    )
