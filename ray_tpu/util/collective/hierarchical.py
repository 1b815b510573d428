"""Hierarchical topology-aware collectives with a quantized DCN hop.

The flat path puts every rank in one world-sized ring, so a group spanning
multiple TPU slices crosses the slow DCN hop with full-precision,
full-world traffic. This module composes the two-level structure the
hardware actually has (MLPerf TPU-v3-pod hierarchical reduction; EQuARX
block-quantized AllReduce — see PAPERS.md):

* **intra-slice (ICI) leg** — reduce-scatter within the slice, so the
  reduction bandwidth rides the fast interconnect;
* **cross-slice (DCN) leg** — the slice *leaders* allreduce the per-slice
  partials across slices, block-int8-quantized (per-block fp32 scale,
  fp32 accumulation at the reducer — ``quantization.py``);
* **all-gather back** — each leader fans the global result back out over
  its slice.

Two engines implement that structure behind one ``Communicator`` surface:

``HierarchicalGroup``
    Host-side composition over per-slice subgroups plus a leader subgroup
    (each with its own coordinator actor) — works on the CPU backend's
    coordinator data plane, i.e. everywhere tests run. DCN failures are
    first-class: a severed or blackholed inter-slice link (fault site
    ``dcn``, ``core/faults.py``) fails the whole gang fast with
    ``PeerUnavailableError`` / ``DeadlineExceededError`` (round-9
    semantics) instead of hanging — the leader propagates the typed error
    to its slice members over the group mailbox.

``XlaHierarchicalGroup``
    The TPU-native engine: one jitted shard_map over a 2-D ``(dcn, ici)``
    device mesh. ``psum_scatter`` over the ici axis, int8 quantize, an
    all-gather over the dcn axis with fp32 accumulation, and an all-gather
    back over ici — the DCN exchange is *sharded* across the slice's
    hosts, so every host fronts only its own shard on the slow hop (the
    shard-wise generalization of the leader group).

Selection happens in ``collective.init_collective_group(strategy=...)``:
``"auto"`` picks hierarchical only when the derived topology spans more
than one slice; ``"flat"`` or ``RAY_TPU_HIERARCHICAL_COLLECTIVES=0``
preserve today's path bit-for-bit.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import numpy as np

from ray_tpu.util import metrics as _metrics
from ray_tpu.util.collective import quantization as quant
from ray_tpu.util.collective.communicator import Communicator
from ray_tpu.util.collective.topology import TwoLevelTopology
from ray_tpu.util.collective.types import (
    ReduceOp,
    like_input,
    to_numpy,
    validate_reducescatter_input,
)

# -- telemetry (satellite: raytpu_collective_* series) ------------------------

_HOP_SECONDS = _metrics.Histogram(
    "raytpu_collective_hop_seconds",
    "wall time of one hierarchical-collective hop, by tier (ici=intra-"
    "slice leg, dcn=cross-slice leg)",
    boundaries=_metrics.LATENCY_BOUNDARIES_S,
    tag_keys=("tier",),
)
_DCN_BYTES_PRE = _metrics.Counter(
    "raytpu_collective_dcn_bytes_pre_total",
    "bytes this rank would ship across the DCN hop at full precision",
)
_DCN_BYTES_POST = _metrics.Counter(
    "raytpu_collective_dcn_bytes_post_total",
    "bytes this rank actually ships across the DCN hop (post-quantization)",
)
_OPS = _metrics.Counter(
    "raytpu_collective_ops_total",
    "hierarchical collective operations started on this rank",
    tag_keys=("op",),
)


def _observe_hop(tier: str, t0: float) -> None:
    if _metrics.metrics_enabled():
        _HOP_SECONDS.observe(time.perf_counter() - t0, {"tier": tier})


def _count_op(op: str) -> None:
    if _metrics.metrics_enabled():
        _OPS.inc(1.0, {"op": op})


def _count_dcn_bytes(pre: int, post: int) -> None:
    if _metrics.metrics_enabled():
        _DCN_BYTES_PRE.inc(float(pre))
        _DCN_BYTES_POST.inc(float(post))


# -- the seeded DCN fault hook ------------------------------------------------


def _dcn_fault_gate(group_name: str, slice_name: str) -> None:
    """Consult the fault plane before crossing the DCN hop. ``dcn.sever``
    fails fast with PeerUnavailableError (link down — the breaker
    semantics); ``dcn.delay`` sleeps, and a delay at or beyond the DCN
    deadline (ms=inf = blackhole) raises DeadlineExceededError after the
    deadline instead of hanging forever. match= globs the group name,
    peer= globs this rank's slice name."""
    from ray_tpu.core import faults

    inj = faults.active()
    if inj is None:
        return
    rule = inj.decide(
        "dcn",
        name=group_name,
        peer=slice_name,
        actions=frozenset({"sever", "delay"}),
    )
    if rule is None:
        return
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.core.errors import (
        DeadlineExceededError,
        PeerUnavailableError,
    )

    if rule.action == "sever":
        raise PeerUnavailableError(
            f"DCN link severed for slice {slice_name!r} "
            f"(collective group {group_name!r}, injected dcn.sever)"
        )
    deadline = GLOBAL_CONFIG.collective_dcn_deadline_s
    if deadline > 0 and rule.delay_s >= deadline:
        time.sleep(deadline)
        raise DeadlineExceededError(
            f"DCN hop for slice {slice_name!r} exceeded the "
            f"{deadline}s deadline (collective group {group_name!r}, "
            f"injected dcn.delay)"
        )
    # A delay under the deadline only slows the hop. With the deadline
    # disabled (<= 0, the round-9 convention) an ms=inf blackhole
    # genuinely hangs — the operator turned the clock off.
    import math as _math

    while rule.delay_s >= _math.inf:
        time.sleep(3600)
    time.sleep(rule.delay_s)


# -- fp32-accumulating quantized reduction (shared by both engines) -----------


def _dequantize_sum(contribs: List[np.ndarray], dtype) -> np.ndarray:
    """The reducer side of the quantized DCN leg: dequantize every
    contribution to fp32 and accumulate in fp32 — quantized payloads are
    never summed in the integer domain. Contributions are self-describing:
    a packed codec buffer is a 1-D uint8 vector; a leader whose partial
    went non-finite ships the raw float tensor instead (float dtypes only
    reach this leg, so uint8 is unambiguous)."""
    total: Optional[np.ndarray] = None
    for buf in contribs:
        buf = to_numpy(buf)
        if buf.dtype == np.uint8:
            part = quant.dequantize_blockwise(quant.unpack(buf))
        else:
            part = buf.astype(np.float32, copy=False)
        total = part if total is None else total + part
    return total.astype(dtype, copy=False)


class HierarchicalGroup(Communicator):
    """Two-level communicator: per-slice subgroups (ICI) + a cross-slice
    leader subgroup (DCN), composed over the host-side data plane.

    Subgroups are ordinary backend communicators with their own
    coordinator actors (``<group>::ici::<i>`` for slice ``i``,
    ``<group>::dcn`` for the leaders); the parent group's coordinator
    doubles as the mailbox for the leader→member fan-out and P2P. The
    ``backend_factory`` indirection keeps this engine backend-agnostic —
    the CPU group is what tests exercise.
    """

    def __init__(
        self,
        group_name: str,
        world_size: int,
        rank: int,
        coordinator,  # parent CollectiveCoordinator handle (mailbox + join)
        timeout_s: float,
        topology: TwoLevelTopology,
        backend_factory,  # (name, world, rank, coord, timeout) -> Communicator
        quantize_dcn: bool = True,
        quant_block: int = quant.DEFAULT_BLOCK,
    ):
        super().__init__(group_name, world_size, rank)
        if topology.world_size != world_size:
            raise ValueError(
                f"topology covers {topology.world_size} ranks but group "
                f"world size is {world_size}"
            )
        self._coord = coordinator
        self._timeout = timeout_s
        self._topo = topology
        self._quantize = bool(quantize_dcn)
        self._block = int(quant_block)
        self._slice_idx = topology.slice_index(rank)
        self._slice_name = topology.slice_name(rank)
        self._local_rank = topology.local_rank(rank)
        self._slice_ranks = topology.ranks_in_slice(self._slice_idx)
        self._is_leader = topology.is_leader(rank)
        self._leader_rank = topology.leader_of_slice(self._slice_idx)
        self._seq = 0  # internal mailbox tag; all ranks issue ops in order
        self._send_tags: dict[int, int] = {}
        self._recv_tags: dict[int, int] = {}
        self._ici: Optional[Communicator] = None
        self._dcn: Optional[Communicator] = None
        # Build ICI first, then DCN: leaders reach the DCN rendezvous only
        # after their slice subgroup is complete, so the two barriers can
        # never interleave into a cross-slice deadlock.
        if len(self._slice_ranks) > 1:
            self._ici = self._make_subgroup(
                f"{group_name}::ici::{self._slice_idx}",
                len(self._slice_ranks),
                self._local_rank,
                backend_factory,
            )
        if self._is_leader and topology.num_slices > 1:
            from ray_tpu.core.config import GLOBAL_CONFIG

            # The DCN subgroup's CALL timeout is the hop deadline: a
            # blackholed peer slice must fail this leader's exchange on
            # the round-9 clock, not the generous whole-group timeout.
            # (The rendezvous coordinator itself keeps the full timeout —
            # group formation legitimately waits for slow slices.)
            ddl = GLOBAL_CONFIG.collective_dcn_deadline_s
            self._dcn = self._make_subgroup(
                f"{group_name}::dcn",
                topology.num_slices,
                self._slice_idx,
                backend_factory,
                call_timeout=min(timeout_s, ddl) if ddl > 0 else timeout_s,
            )

    def _make_subgroup(
        self, name, world, rank, backend_factory, call_timeout=None
    ):
        from ray_tpu.util.collective.collective import _coordinator_handle

        coord, _ = _coordinator_handle(name, world, rank, self._timeout)
        return backend_factory(
            name, world, rank, coord, call_timeout or self._timeout
        )

    # -- introspection -------------------------------------------------------

    @property
    def backend(self) -> str:
        return "hierarchical"

    @property
    def topology(self) -> TwoLevelTopology:
        return self._topo

    @property
    def quantized_dcn(self) -> bool:
        return self._quantize

    # -- mailbox helpers (leader <-> member fan-out over the parent coord) ---

    def _post(self, dst_rank: int, tag: str, payload) -> None:
        import ray_tpu

        ray_tpu.get(
            self._coord.post.remote(self._rank, int(dst_rank), tag, payload),
            timeout=self._timeout,
        )

    def _take(self, src_rank: int, tag: str):
        import ray_tpu

        return ray_tpu.get(
            self._coord.take.remote(int(src_rank), self._rank, tag),
            timeout=self._timeout * 2,
        )

    def _fan_out(self, tag: str, payload) -> None:
        """Leader -> every other member of this slice."""
        import ray_tpu

        refs = [
            self._coord.post.remote(self._rank, m, tag, payload)
            for m in self._slice_ranks
            if m != self._rank
        ]
        if refs:
            ray_tpu.get(refs, timeout=self._timeout)

    def _take_or_raise(self, tag: str):
        """Member side of the fan-out: a leader that failed its DCN hop
        posts a typed error instead of a value — re-raise it here so the
        whole slice fails fast with round-9 semantics, never a hang."""
        kind, *rest = self._take(self._leader_rank, tag)
        if kind == "err":
            from ray_tpu.core import errors as _errors

            cls = getattr(_errors, rest[0], RuntimeError)
            raise cls(rest[1])
        return rest[0]

    def _next_tag(self, op: str) -> str:
        self._seq += 1
        return f"hier::{op}::{self._seq}"

    def _dcn_exchange(self, fn):
        """One DCN hop: consult the fault plane, time the leg, and convert
        a hop that outran the DCN call timeout (a real blackholed link, or
        a peer slice that severed) into DeadlineExceededError — the
        round-9 contract holds outside fault injection too."""
        from ray_tpu.core.errors import (
            DeadlineExceededError,
            PeerUnavailableError,
            TaskError,
        )

        _dcn_fault_gate(self._group_name, self._slice_name)
        t0 = time.perf_counter()
        try:
            return fn()
        except (DeadlineExceededError, PeerUnavailableError):
            raise
        except Exception as e:  # noqa: BLE001 — classify, then re-raise
            timed_out = isinstance(e, TimeoutError) or (
                isinstance(e, TaskError) and "timed out" in str(e)
            )
            if timed_out:
                raise DeadlineExceededError(
                    f"DCN hop for slice {self._slice_name!r} (collective "
                    f"group {self._group_name!r}) did not complete within "
                    f"its deadline"
                ) from e
            raise
        finally:
            _observe_hop("dcn", t0)

    # -- the three-legged allreduce ------------------------------------------

    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        _count_op("allreduce")
        return self._allreduce(tensor, ReduceOp(op))

    def _allreduce(self, tensor, op: ReduceOp):
        arr = to_numpy(tensor)
        tag = self._next_tag("ar")
        partial = self._reduced_at_leader(arr, op, tag)
        if self._is_leader:
            t0 = time.perf_counter()
            self._fan_out(tag + "::out", ("ok", partial))
            _observe_hop("ici", t0)
            return like_input(tensor, partial)
        out = self._take_or_raise(tag + "::out")
        return like_input(tensor, out)

    def _reduced_at_leader(self, arr, op: ReduceOp, tag: str):
        """ICI reduce + DCN exchange; the full reduced tensor on leaders,
        None elsewhere. A leader whose DCN leg fails fans the typed error
        to its slice members (every member of every op waits on the
        ``::out`` tag, so the error always has an audience) before
        re-raising."""
        partial = self._intra_reduce(arr, op, tag)
        if self._is_leader and self._dcn is not None:
            try:
                partial = self._dcn_allreduce(partial, op)
            except Exception as e:  # noqa: BLE001 — must unblock the slice
                self._fan_out(tag + "::out", ("err", type(e).__name__, str(e)))
                raise
        return partial

    def _intra_reduce(self, arr: np.ndarray, op: ReduceOp, tag: str):
        """ICI leg: reduce-scatter within the slice (each rank reduces its
        own shard), shards converge on the leader via the mailbox. Falls
        back to a coordinator reduce when dim0 does not split evenly.
        Returns the full slice partial on the leader, None elsewhere."""
        if self._ici is None:
            return arr if self._is_leader else None
        k = len(self._slice_ranks)
        t0 = time.perf_counter()
        if arr.ndim >= 1 and arr.shape[0] % k == 0:
            shard = to_numpy(self._ici.reducescatter(arr, op))
            if self._is_leader:
                import ray_tpu

                # One batched get, not k-1 serial round trips: the shard
                # takes are independent and the mailbox posts them as the
                # members arrive.
                rest = ray_tpu.get(
                    [
                        self._coord.take.remote(
                            self._slice_ranks[local], self._rank,
                            tag + "::sh",
                        )
                        for local in range(1, k)
                    ],
                    timeout=self._timeout * 2,
                )
                partial = np.concatenate([shard, *rest], axis=0)
            else:
                self._post(self._leader_rank, tag + "::sh", shard)
                partial = None
        else:
            out = self._ici.reduce(arr, dst_rank=0, op=op)
            partial = to_numpy(out) if self._is_leader else None
        _observe_hop("ici", t0)
        return partial

    def _dcn_allreduce(self, partial: np.ndarray, op: ReduceOp) -> np.ndarray:
        """DCN leg (leaders only): block-int8-quantized for SUM over float
        tensors, full precision otherwise. Every leader dequantizes and
        accumulates in fp32, in slice order, so all leaders hold the
        bitwise-identical result."""

        def hop():
            if (
                self._quantize
                and op == ReduceOp.SUM
                and quant.should_quantize(partial)
            ):
                # Every leader takes this leg (op kinds must line up at
                # the coordinator), but each decides independently what to
                # ship: the packed codec buffer, or — when its partial
                # went non-finite (mixed-precision gradient overflow) —
                # the raw float tensor, so the inf reaches every rank
                # intact for the AMP scaler instead of a nan-poisoned
                # block. Payloads are self-describing (uint8 = packed).
                if bool(np.isfinite(partial).all()):
                    payload: np.ndarray = quant.pack(
                        quant.quantize_blockwise(partial, self._block)
                    )
                else:
                    payload = partial
                _count_dcn_bytes(pre=partial.nbytes, post=payload.nbytes)
                contribs = self._dcn.allgather(payload)
                return _dequantize_sum(contribs, partial.dtype)
            _count_dcn_bytes(pre=partial.nbytes, post=partial.nbytes)
            return to_numpy(self._dcn.allreduce(partial, op))

        return self._dcn_exchange(hop)

    # -- remaining collectives -----------------------------------------------

    def barrier(self) -> None:
        _count_op("barrier")
        # A scalar allreduce IS a barrier (the XlaGroup precedent), and it
        # inherits the whole fail-fast machinery: a DCN fault on the
        # leader fans out as a typed error instead of stranding members in
        # a bare ICI barrier until the group timeout.
        self._allreduce(np.zeros((), np.float32), ReduceOp.SUM)

    def reduce(self, tensor, dst_rank: int = 0, op: ReduceOp = ReduceOp.SUM):
        """Reduce to ``dst_rank``: every member waits on the op (a tiny ack
        for non-destinations), but only the destination receives the full
        tensor — the fan-out cost is O(1), not O(slice)."""
        import ray_tpu

        _count_op("reduce")
        dst = int(dst_rank)
        arr = to_numpy(tensor)
        tag = self._next_tag("rd")
        partial = self._reduced_at_leader(arr, ReduceOp(op), tag)
        if self._is_leader:
            refs = [
                self._coord.post.remote(
                    self._rank, m, tag + "::out",
                    ("ok", partial if m == dst else None),
                )
                for m in self._slice_ranks
                if m != self._rank
            ]
            if refs:
                ray_tpu.get(refs, timeout=self._timeout)
            return like_input(tensor, partial) if self._rank == dst else tensor
        out = self._take_or_raise(tag + "::out")
        return like_input(tensor, out) if self._rank == dst else tensor

    def broadcast(self, tensor, src_rank: int = 0):
        _count_op("broadcast")
        src_rank = int(src_rank)
        tag = self._next_tag("bc")
        src_slice = self._topo.slice_index(src_rank)
        if self._rank == src_rank:
            value = to_numpy(tensor)
            if not self._is_leader:
                self._post(self._leader_rank, tag + "::up", value)
                value = self._take_or_raise(tag + "::out")
            else:
                value = self._leader_broadcast(value, src_slice, tag)
            return like_input(tensor, value)
        if self._is_leader:
            up = (
                self._take(src_rank, tag + "::up")
                if self._slice_idx == src_slice
                else None
            )
            value = self._leader_broadcast(up, src_slice, tag)
            return like_input(tensor, value)
        return like_input(tensor, self._take_or_raise(tag + "::out"))

    def _leader_broadcast(self, value, src_slice: int, tag: str):
        """Leader side of broadcast: cross the DCN hop, then fan out."""
        try:
            if self._dcn is not None:
                seed = value if value is not None else np.zeros(0, np.uint8)
                value = self._dcn_exchange(
                    lambda: to_numpy(
                        self._dcn.broadcast(seed, src_rank=src_slice)
                    )
                )
        except Exception as e:  # noqa: BLE001 — must unblock the slice
            self._fan_out(tag + "::out", ("err", type(e).__name__, str(e)))
            raise
        self._fan_out(tag + "::out", ("ok", value))
        return value

    def allgather(self, tensor) -> List[Any]:
        _count_op("allgather")
        arr = to_numpy(tensor)
        tag = self._next_tag("ag")
        if not self._is_leader:
            self._post(self._leader_rank, tag + "::up", arr)
            parts = self._take_or_raise(tag + "::out")
            return [like_input(tensor, p) for p in parts]
        import ray_tpu

        parts = [arr] + ray_tpu.get(
            [
                self._coord.take.remote(m, self._rank, tag + "::up")
                for m in self._slice_ranks[1:]
            ],
            timeout=self._timeout * 2,
        )
        try:
            if self._dcn is not None:
                slice_stack = np.stack(parts, axis=0)
                per_slice = self._dcn_exchange(
                    lambda: self._dcn.allgather(slice_stack)
                )
                # Slice order == contiguous global rank order (topology
                # contract), so flattening reassembles rank order exactly.
                parts = [
                    to_numpy(s)[i]
                    for s in per_slice
                    for i in range(to_numpy(s).shape[0])
                ]
        except Exception as e:  # noqa: BLE001 — must unblock the slice
            self._fan_out(tag + "::out", ("err", type(e).__name__, str(e)))
            raise
        self._fan_out(tag + "::out", ("ok", parts))
        return [like_input(tensor, p) for p in parts]

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        """Each member receives only ITS world-chunk of the reduced tensor
        from the leader — 1/world of the mailbox traffic a full allreduce
        fan-out would ship."""
        import ray_tpu

        _count_op("reducescatter")
        arr = to_numpy(tensor)
        validate_reducescatter_input(arr, self._world_size)
        tag = self._next_tag("rs")
        partial = self._reduced_at_leader(arr, ReduceOp(op), tag)
        chunk = arr.shape[0] // self._world_size
        if self._is_leader:
            refs = [
                self._coord.post.remote(
                    self._rank, m, tag + "::out",
                    ("ok", partial[m * chunk : (m + 1) * chunk]),
                )
                for m in self._slice_ranks
                if m != self._rank
            ]
            if refs:
                ray_tpu.get(refs, timeout=self._timeout)
            return like_input(
                tensor,
                partial[self._rank * chunk : (self._rank + 1) * chunk],
            )
        return like_input(tensor, self._take_or_raise(tag + "::out"))

    # -- P2P: the parent coordinator mailbox, same contract as CpuGroup -----

    def send(self, tensor, dst_rank: int) -> None:
        tag = self._send_tags.get(dst_rank, 0)
        self._send_tags[dst_rank] = tag + 1
        self._post(dst_rank, tag, to_numpy(tensor))

    def recv(self, src_rank: int):
        tag = self._recv_tags.get(src_rank, 0)
        self._recv_tags[src_rank] = tag + 1
        return self._take(src_rank, tag)

    def destroy(self) -> None:
        from ray_tpu.util.collective.collective import _teardown_group_state

        for sub in (self._ici, self._dcn):
            if sub is None:
                continue
            sub.destroy()
            if sub.rank == 0:
                _teardown_group_state(sub.group_name)
        self._ici = None
        self._dcn = None


# -- the single-program XLA engine -------------------------------------------


def build_xla_hier_allreduce(
    hmesh, lax_op: str, quantized: bool, shape: tuple, n: int, k: int,
    shard_len: int, block: int,
):
    """The jitted three-leg program over a 2-D ``(dcn, ici)`` mesh:
    ``psum_scatter`` over ici (each host owns a shard of the slice
    partial), the DCN exchange — int8 payload + fp32 scales, fp32
    accumulation — over dcn, and an all-gather back over ici.

    A free function (not a method) so the program is testable on a
    single-process multi-device mesh: the 8 virtual CPU devices stand in
    for 2 slices x 4 hosts exactly as they do for the train-tier SPMD
    tests. ``n`` is the element count, ``k`` the ici axis size,
    ``shard_len`` the per-host shard (a whole number of quantization
    blocks, padded)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    pad = k * shard_len - n

    def body(x):
        import jax.lax as lax

        flat = x[0].reshape(-1)
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        # ICI leg: reduce-scatter — each host owns one shard of the
        # slice partial.
        shard = lax.psum_scatter(
            flat, "ici", scatter_dimension=0, tiled=True
        )
        if quantized:
            blocks = shard.astype(jnp.float32).reshape(-1, block)
            absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
            scale = absmax / 127.0
            safe = jnp.where(scale > 0, scale, 1.0)
            q = jnp.clip(jnp.round(blocks / safe), -127, 127).astype(
                jnp.int8
            )
            # DCN leg: int8 payload + fp32 scales cross the slow hop;
            # accumulate in fp32 on arrival.
            qs = lax.all_gather(q, "dcn")
            ss = lax.all_gather(scale, "dcn")
            reduced = (
                (qs.astype(jnp.float32) * ss)
                .sum(axis=0)
                .reshape(-1)
                .astype(x.dtype)
            )
        else:
            reduced = getattr(lax, lax_op)(shard, "dcn")
        # All-gather back over ICI: every host reassembles the full
        # tensor.
        full = lax.all_gather(reduced.reshape(-1), "ici").reshape(-1)
        return full[:n].reshape(shape)

    return jax.jit(
        jax.shard_map(
            body,
            mesh=hmesh,
            in_specs=P(("dcn", "ici")),
            out_specs=P(),
            check_vma=False,
        )
    )


def _build_xla_hierarchical():
    from ray_tpu.util.collective.xla_group import XlaGroup

    class _XlaHierarchicalGroup(XlaGroup):
        """Hierarchical + quantized allreduce inside ONE jitted shard_map
        program over a 2-D ``(dcn, ici)`` mesh: ``psum_scatter`` over ici,
        int8 quantize, all-gather over dcn with fp32 accumulation, gather
        back over ici. XLA lowers the ici legs onto the intra-slice
        interconnect and the dcn exchange onto the cross-slice network; the
        int8 payload is what crosses the slow hop. Collectives other than
        allreduce/reduce/barrier inherit the flat 1-D path — they are
        control-plane-rare and correctness-identical.

        Requires a uniform topology (equal ranks per slice): real TPU
        multi-slice jobs reserve identical slices (SlicePlacementGroup), so
        non-uniform groups fall back to flat at selection time.
        """

        def __init__(
            self,
            group_name,
            world_size,
            rank,
            coordinator,
            timeout_s,
            topology: TwoLevelTopology,
            quantize_dcn: bool = True,
            quant_block: int = quant.DEFAULT_BLOCK,
        ):
            if not topology.uniform or not topology.spans_dcn:
                raise ValueError(
                    "XlaHierarchicalGroup needs a uniform multi-slice "
                    "topology (equal ranks per slice, >1 slice)"
                )
            self._topo = topology
            self._quantize = bool(quantize_dcn)
            self._block = int(quant_block)
            self._slice_name = topology.slice_name(rank)
            super().__init__(
                group_name, world_size, rank, coordinator, timeout_s
            )
            self._build_hmesh()

        @property
        def backend(self) -> str:
            return "xla-hierarchical"

        @property
        def topology(self) -> TwoLevelTopology:
            return self._topo

        @property
        def quantized_dcn(self) -> bool:
            return self._quantize

        def _build_hmesh(self) -> None:
            from jax.sharding import Mesh

            num_slices = self._topo.num_slices
            per_slice = self._world_size // num_slices
            devs = np.empty(self._world_size, dtype=object)
            for i, d in enumerate(self._devices):
                devs[i] = d
            self._hmesh = Mesh(
                devs.reshape(num_slices, per_slice), ("dcn", "ici")
            )

        def _hier_global_array(self, tensor):
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            # Device path for jax arrays, like XlaGroup._global_array: a
            # device-resident gradient enters the program without a host
            # round trip.
            if isinstance(tensor, jax.Array):
                local = jax.device_put(tensor, self._my_device)
            else:
                local = jax.device_put(
                    jnp.asarray(to_numpy(tensor)), self._my_device
                )
            local = local[None]
            sharding = NamedSharding(self._hmesh, P(("dcn", "ici")))
            return jax.make_array_from_single_device_arrays(
                (self._world_size, *local.shape[1:]), sharding, [local]
            )

        def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
            import jax

            op = ReduceOp(op)
            if op != ReduceOp.SUM:
                # The ici leg of the three-leg program is a psum_scatter;
                # composing it with pmax/pmin on the dcn axis would reduce
                # per-slice SUMS, not the requested op. Non-SUM allreduces
                # are control-plane-rare: ride the flat 1-D path.
                return super().allreduce(tensor, op)
            # Only shape/dtype metadata is needed host-side; jax arrays
            # stay on device (should_quantize and .dtype.itemsize read
            # the dtype object, not the buffer).
            arr = tensor if isinstance(tensor, jax.Array) else to_numpy(tensor)
            quantized = self._quantize and quant.should_quantize(arr)
            _count_op("allreduce")
            # NB: on the single-program engine the gate can only stop THIS
            # process's hop. A one-sided rule (peer= globbing one slice)
            # leaves the other slices inside the jitted exchange, bounded
            # by the JAX runtime's own collective/coordination timeout —
            # not collective_dcn_deadline_s. Symmetric rules (peer=*) fail
            # every slice fast; the host engine bounds both cases itself.
            _dcn_fault_gate(self._group_name, self._slice_name)
            num_slices = self._topo.num_slices
            k = self._world_size // num_slices
            n = int(arr.size)
            # Shards must be whole blocks so per-block scales never span a
            # shard boundary.
            shard_len = -(-n // (k * self._block)) * self._block
            itemsize = arr.dtype.itemsize
            if quantized:
                # post: int8 payload + one fp32 scale per block (the codec
                # is int8/fp32 regardless of input dtype).
                _count_dcn_bytes(
                    pre=shard_len * itemsize,
                    post=shard_len + 4 * (shard_len // self._block),
                )
            else:
                _count_dcn_bytes(
                    pre=shard_len * itemsize, post=shard_len * itemsize
                )
            t0 = time.perf_counter()
            fn = self._hier_fn(op, quantized, arr.shape, n, k, shard_len)
            garr = self._hier_global_array(arr)
            out = fn(garr)
            shard = [
                s.data
                for s in out.addressable_shards
                if s.device == self._my_device
            ][0]
            _observe_hop("dcn", t0)
            # Device-resident result (jax array), matching XlaGroup._run:
            # a gradient goes back into the jitted apply with no
            # device->host->device bounce.
            return shard

        def _hier_fn(self, op, quantized, shape, n, k, shard_len):
            key = ("h_allreduce", op, quantized, shape)
            fn = self._jitted.get(key)
            if fn is not None:
                return fn
            from ray_tpu.util.collective.xla_group import _REDUCE_LAX

            fn = build_xla_hier_allreduce(
                self._hmesh, _REDUCE_LAX[ReduceOp(op)], quantized, shape,
                n, k, shard_len, self._block,
            )
            self._jitted[key] = fn
            return fn

        def reduce(
            self, tensor, dst_rank: int = 0, op: ReduceOp = ReduceOp.SUM
        ):
            out = self.allreduce(tensor, op)
            return out if self._rank == int(dst_rank) else tensor

    return _XlaHierarchicalGroup


_XLA_HIER_CLS = None


def xla_hierarchical_group(*args, **kwargs):
    """Lazy constructor: jax imports only when an XLA group is built."""
    global _XLA_HIER_CLS
    if _XLA_HIER_CLS is None:
        _XLA_HIER_CLS = _build_xla_hierarchical()
    return _XLA_HIER_CLS(*args, **kwargs)
