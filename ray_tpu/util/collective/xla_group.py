"""XLA collective group — device collectives compiled onto ICI/DCN.

This is the TPU-native replacement for the reference's NCCLGroup
(python/ray/util/collective/collective_group/nccl_collective_group.py:121).
Instead of cupy-NCCL comms keyed by a NCCLUniqueID, the group is a
multi-controller JAX runtime: rank 0 hosts the JAX coordination service
(rendezvous address published through the group coordinator actor, the
analog of NCCLUniqueIDStore), every rank calls
``jax.distributed.initialize``, and each collective is a jitted
``shard_map`` over a 1-D mesh with one device per process — XLA lowers it
to ICI collectives within a slice and DCN collectives across slices.

Host-side P2P send/recv rides the coordinator mailbox (device-direct P2P
belongs to compiled-graph channels, where both ends run one program).
"""

from __future__ import annotations

from typing import Any, List

from ray_tpu.util.collective.communicator import Communicator
from ray_tpu.util.collective.types import (
    ReduceOp,
    to_numpy,
    validate_reducescatter_input,
)

_REDUCE_LAX = {
    ReduceOp.SUM: "psum",
    ReduceOp.MAX: "pmax",
    ReduceOp.MIN: "pmin",
    ReduceOp.PRODUCT: "pprod",  # no lax primitive; reducescatter emulates
}


from ray_tpu.util.net import free_port as _free_port, local_ip as _local_ip


class XlaGroup(Communicator):
    def __init__(
        self,
        group_name: str,
        world_size: int,
        rank: int,
        coordinator,  # CollectiveCoordinator handle (rendezvous + P2P mailbox)
        timeout_s: float = 120.0,
    ):
        super().__init__(group_name, world_size, rank)
        self._coord = coordinator
        self._timeout = timeout_s
        self._send_tags: dict[int, int] = {}
        self._recv_tags: dict[int, int] = {}
        self._jitted: dict = {}
        self._rendezvous()
        self._build_mesh()

    @property
    def backend(self) -> str:
        return "xla"

    # -- bootstrap -----------------------------------------------------------

    def _rendezvous(self) -> None:
        import jax
        import ray_tpu

        if self._world_size == 1:
            return
        # NB: don't probe jax.process_count() here — it would initialize the
        # XLA backend, after which jax.distributed.initialize() refuses to run.
        if jax.distributed.is_initialized():
            # Multi-controller runtime already up (e.g. the train tier ran
            # jax.distributed.initialize); reuse it.
            if jax.process_count() != self._world_size:
                raise RuntimeError(
                    f"existing JAX runtime has {jax.process_count()} "
                    f"processes but group wants {self._world_size}"
                )
            return
        key = "xla_coordinator"
        if self._rank == 0:
            addr = f"{_local_ip()}:{_free_port()}"
            ray_tpu.get(self._coord.put_meta.remote(key, addr))
        else:
            addr = ray_tpu.get(
                self._coord.get_meta.remote(key), timeout=self._timeout
            )
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=self._world_size,
            process_id=self._rank,
            initialization_timeout=int(self._timeout),
        )

    def _build_mesh(self) -> None:
        import jax
        from jax.sharding import Mesh

        if self._world_size == 1:
            self._my_device = jax.local_devices()[0]
            self._devices = [self._my_device]
            self._mesh = Mesh([self._my_device], ("ranks",))
            return
        by_proc: dict[int, Any] = {}
        for d in sorted(jax.devices(), key=lambda d: (d.process_index, d.id)):
            by_proc.setdefault(d.process_index, d)
        if len(by_proc) != self._world_size:
            raise RuntimeError(
                f"JAX runtime spans {len(by_proc)} processes; group wants "
                f"{self._world_size}"
            )
        devices = [by_proc[p] for p in sorted(by_proc)]
        self._my_device = by_proc[jax.process_index()]
        # Rank-ordered device list: XlaHierarchicalGroup reshapes it into
        # the 2-D (dcn, ici) mesh.
        self._devices = devices
        self._mesh = Mesh(devices, ("ranks",))

    # -- device data plane ---------------------------------------------------

    def _global_array(self, tensor):
        """Stack local tensors into a global (world, *shape) array sharded
        one-rank-per-device along axis 0.

        jax arrays take the device path: device_put moves (or no-ops) the
        existing buffer without a host round-trip, so a device-resident
        gradient never touches host memory on its way into the collective
        (the rllib learner's flat-gradient allreduce rides this)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if isinstance(tensor, jax.Array):
            local = jax.device_put(tensor, self._my_device)
        else:
            local = jax.device_put(
                jnp.asarray(to_numpy(tensor)), self._my_device
            )
        local = local[None]
        sharding = NamedSharding(self._mesh, P("ranks"))
        return jax.make_array_from_single_device_arrays(
            (self._world_size, *local.shape[1:]), sharding, [local]
        )

    def _run(self, kind: str, tensor, **static):
        """jit(shard_map(op)) over the ranks mesh; returns this process's
        local shard of the result (device-resident)."""
        import jax
        from jax.sharding import PartitionSpec as P

        garr = self._global_array(tensor)
        cache_key = (kind, tuple(sorted(static.items())))
        fn = self._jitted.get(cache_key)
        if fn is None:
            if kind == "allreduce":
                lax_op = static["op"]

                def body(x):
                    import jax.lax as lax

                    return getattr(lax, lax_op)(x, "ranks")[0]

                out_spec = P()
            elif kind == "allgather":

                def body(x):
                    import jax.lax as lax

                    return lax.all_gather(x[0], "ranks")

                out_spec = P()
            elif kind == "broadcast":
                src = static["src_rank"]

                def body(x):
                    import jax.lax as lax

                    return lax.all_gather(x[0], "ranks")[src]

                out_spec = P()
            elif kind == "reducescatter":
                red = static.get("op", "psum")
                if red == "psum":

                    def body(x):
                        import jax.lax as lax

                        return lax.psum_scatter(
                            x[0], "ranks", scatter_dimension=0, tiled=True
                        )

                else:
                    # MIN/MAX/PRODUCT: no fused lax scatter-reduce exists;
                    # all-gather + elementwise reduce + take this rank's
                    # tile. Costs one all-gather more than psum_scatter —
                    # fine for these rare ops.
                    import jax.numpy as jnp

                    reducer = {
                        "pmin": jnp.min,
                        "pmax": jnp.max,
                        "pprod": jnp.prod,
                    }[red]

                    def body(x):
                        import jax.lax as lax

                        full = reducer(
                            lax.all_gather(x[0], "ranks"), axis=0
                        )
                        if full.shape[0] % self._world_size:
                            # Match the SUM path and the cpu backend: an
                            # indivisible dim0 must raise, never silently
                            # truncate.
                            raise ValueError(
                                f"reducescatter dim0 {full.shape[0]} not "
                                f"divisible by world {self._world_size}"
                            )
                        chunk = full.shape[0] // self._world_size
                        return lax.dynamic_slice_in_dim(
                            full,
                            lax.axis_index("ranks") * chunk,
                            chunk,
                            axis=0,
                        )

                out_spec = P("ranks")
            else:
                raise ValueError(kind)
            fn = jax.jit(
                jax.shard_map(
                    body,
                    mesh=self._mesh,
                    in_specs=P("ranks"),
                    out_specs=out_spec,
                    # Replication of all_gather/psum outputs is semantic here;
                    # the varying-axes checker can't always infer it.
                    check_vma=False,
                )
            )
            self._jitted[cache_key] = fn
        out = fn(garr)
        # My share: the addressable shard this process holds — returned
        # DEVICE-RESIDENT (a jax array). Callers that want host values
        # wrap with np.asarray; keeping the buffer on device lets
        # allreduce feed straight back into a jitted update with no
        # device->host->device bounce.
        return [
            s.data for s in out.addressable_shards
            if s.device == self._my_device
        ][0]

    # -- Communicator API ----------------------------------------------------

    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        import jax.numpy as jnp

        op = ReduceOp(op)
        if op == ReduceOp.PRODUCT:
            # lax has no pprod; allgather then multiply (rare op, small cost).
            gathered = self._run("allgather", tensor)
            return jnp.asarray(gathered).prod(axis=0)
        return jnp.asarray(self._run("allreduce", tensor, op=_REDUCE_LAX[op]))

    def barrier(self) -> None:
        import numpy as np

        self._run("allreduce", np.zeros((), np.float32), op="psum")

    def reduce(self, tensor, dst_rank: int = 0, op: ReduceOp = ReduceOp.SUM):
        # XLA collectives are bulk-synchronous: an all-reduce then discard on
        # non-destination ranks costs the same ICI traffic as a tree reduce
        # at these message sizes and keeps the program SPMD.
        out = self.allreduce(tensor, op)
        return out if self._rank == int(dst_rank) else tensor

    def broadcast(self, tensor, src_rank: int = 0):
        import jax.numpy as jnp

        return jnp.asarray(
            self._run("broadcast", tensor, src_rank=int(src_rank))
        )

    def allgather(self, tensor) -> List[Any]:
        import jax.numpy as jnp

        stacked = self._run("allgather", tensor)
        return [jnp.asarray(stacked[i]) for i in range(self._world_size)]

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        import jax.numpy as jnp

        op = ReduceOp(op)
        # Validate before tracing: psum_scatter on an indivisible dim0
        # would otherwise surface as a backend-dependent shape error from
        # inside XLA; the cpu backend raises the same ValueError. The
        # check only reads .shape — no device-to-host copy.
        validate_reducescatter_input(tensor, self._world_size)
        return jnp.asarray(
            self._run("reducescatter", tensor, op=_REDUCE_LAX[op])
        )

    def send(self, tensor, dst_rank: int) -> None:
        import ray_tpu

        tag = self._send_tags.get(dst_rank, 0)
        self._send_tags[dst_rank] = tag + 1
        ray_tpu.get(
            self._coord.post.remote(
                self._rank, int(dst_rank), tag, to_numpy(tensor)
            ),
            timeout=self._timeout,
        )

    def recv(self, src_rank: int):
        import jax.numpy as jnp
        import ray_tpu

        tag = self._recv_tags.get(src_rank, 0)
        self._recv_tags[src_rank] = tag + 1
        return jnp.asarray(
            ray_tpu.get(
                self._coord.take.remote(int(src_rank), self._rank, tag),
                timeout=self._timeout * 2,
            )
        )

    def destroy(self) -> None:
        self._jitted.clear()
