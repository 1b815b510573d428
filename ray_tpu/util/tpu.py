"""TPU slice orchestration: whole-slice gang reservation + multi-slice env.

Reference parity: python/ray/util/tpu.py (491 LoC) — worker-resource math
(get_tpu_worker_resources :131), MegaScale DCN coordination env
(get_tpu_coordinator_env_vars :196), and `SlicePlacementGroup` (:223) which
reserves whole TPU slices: first grab the singleton ``TPU-<pod>-head``
resource (worker 0 of some slice) with a label-selector placement group,
learn that slice's name, then reserve one bundle per host of the named slice.

The slice — not the chip — is the first-class scheduling unit here: a
reservation yields a stable, gap-free host set whose workers can form one
jax.distributed world with contiguous process indices over ICI.
"""

from __future__ import annotations

import math
from typing import Optional

from ray_tpu.accelerators.tpu import (
    TPU_SLICE_NAME_LABEL,
    chips_per_host as _chips_per_host_for_pod,
    num_chips_from_topology,
    num_chips_in_pod,
    pod_type_from_topology,
    tpu_generation,
    valid_pod_type,
)
from ray_tpu.util.placement_group import (
    PlacementGroup,
    placement_group,
    remove_placement_group,
)

__all__ = [
    "get_tpu_version_from_type",
    "get_current_pod_name",
    "get_current_pod_worker_count",
    "get_num_tpu_chips_on_node",
    "get_tpu_worker_resources",
    "get_tpu_num_slices_for_workers",
    "get_tpu_coordinator_env_vars",
    "SlicePlacementGroup",
    "slice_placement_group",
]


def get_tpu_version_from_type(accelerator_type: str) -> str:
    """``"v4-16"`` or ``"TPU-V4"`` → ``"v4"``."""
    t = accelerator_type
    if t.upper().startswith("TPU-"):
        return t[4:].lower()
    return tpu_generation(t)


def get_current_pod_name() -> Optional[str]:
    from ray_tpu.accelerators.tpu import TPUAcceleratorManager

    return TPUAcceleratorManager.get_current_node_tpu_name()


def get_current_pod_worker_count() -> Optional[int]:
    from ray_tpu.accelerators.tpu import TPUAcceleratorManager

    pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
    if pod_type is None:
        return None
    from ray_tpu.accelerators.tpu import num_hosts_in_pod

    return num_hosts_in_pod(pod_type)


def get_num_tpu_chips_on_node() -> int:
    from ray_tpu.accelerators.tpu import TPUAcceleratorManager

    return TPUAcceleratorManager.get_current_node_num_accelerators()


def _chips_per_host(topology: str, accelerator_version: str) -> int:
    """Chips per host for a topology: full slices smaller than one host
    live on a partial host."""
    total = num_chips_from_topology(topology)
    return min(
        total,
        _chips_per_host_for_pod(pod_type_from_topology(topology, accelerator_version)),
    )


def get_tpu_worker_resources(
    topology: str,
    accelerator_type: str,
    resources_per_unit: Optional[dict] = None,
    num_slices: int = 1,
) -> tuple:
    """(num_workers, per-worker resources) to cover ``num_slices`` slices of
    ``topology``. Default unit is one host's chips; explicit TPU counts must
    divide both the slice and the total evenly (no worker may straddle a
    slice boundary — its jax.distributed world must sit on one ICI domain).
    """
    version = get_tpu_version_from_type(accelerator_type)
    cph = _chips_per_host(topology, version)
    chips_per_slice = num_chips_from_topology(topology)
    total_chips = chips_per_slice * num_slices

    unit = dict(resources_per_unit or {})
    unit.setdefault("CPU", 1)
    unit.setdefault("TPU", cph)
    tpus_per_unit = unit["TPU"]
    if tpus_per_unit <= 0:
        raise ValueError("TPU resources must be positive.")
    if total_chips % tpus_per_unit != 0:
        raise ValueError(
            f"total chips ({total_chips}) not divisible by TPU per unit "
            f"({tpus_per_unit})"
        )
    if chips_per_slice % tpus_per_unit != 0:
        raise ValueError(
            f"{tpus_per_unit} TPU chips per unit does not divide the "
            f"{chips_per_slice} chips of one slice: workers would straddle "
            "slice boundaries"
        )
    return int(total_chips // tpus_per_unit), unit


def get_tpu_num_slices_for_workers(
    topology: str,
    accelerator_type: str,
    num_workers: int,
    resources_per_worker: Optional[dict] = None,
) -> int:
    """Slices needed for ``num_workers`` workers (1 on invalid input)."""
    if not topology or not accelerator_type:
        return 1
    try:
        per_slice, _ = get_tpu_worker_resources(
            topology, accelerator_type, resources_per_worker, num_slices=1
        )
        if per_slice == 0:
            return 1
        return max(1, math.ceil(num_workers / per_slice))
    except Exception:  # raylint: disable=RL006 -- host-count math over partial metadata; 1 is the safe minimum
        return 1


def get_tpu_coordinator_env_vars(
    coordinator_address: str,
    num_slices: int,
    slice_id: int,
    coordinator_port: str = "8081",
) -> dict:
    """MegaScale env for a worker of slice ``slice_id`` in a multi-slice
    (DCN-spanning) job (reference: util/tpu.py:196)."""
    return {
        "MEGASCALE_COORDINATOR_ADDRESS": coordinator_address,
        "MEGASCALE_PORT": str(coordinator_port),
        "MEGASCALE_NUM_SLICES": str(num_slices),
        "MEGASCALE_SLICE_ID": str(slice_id),
    }


class SlicePlacementGroup:
    """Gang reservation of ``num_slices`` whole TPU slices.

    Protocol (reference: util/tpu.py:345 `_reserve_slice`):

    1. For each slice, create a single-bundle placement group demanding the
       singleton ``TPU-<pod_type>-head`` resource. Only worker-0 hosts
       advertise it, and each advertises exactly 1 — so each head group
       claims exclusive ownership of one distinct slice.
    2. Read the slice name off the head node's ``ray.io/tpu-slice-name``
       label.
    3. Create the main placement group: one bundle per host across all
       reserved slices, each demanding that host's chips, pinned to its
       slice by a per-bundle label selector.

    The head groups are kept until `shutdown()` — they are the mutual
    exclusion tokens preventing double-reservation of a slice.
    """

    def __init__(
        self,
        topology: Optional[str] = None,
        accelerator_version: str = "v4",
        num_slices: int = 1,
        pod_type: Optional[str] = None,
        timeout: float = 100.0,
    ):
        if pod_type is None:
            if topology is None:
                raise ValueError("need topology or pod_type")
            pod_type = pod_type_from_topology(
                topology, accelerator_version.lower()
            )
        if not valid_pod_type(pod_type):
            raise ValueError(f"invalid pod type {pod_type!r}")
        if num_slices < 1:
            raise ValueError("num_slices must be >= 1")
        self._pod_type = pod_type
        self._accelerator_version = tpu_generation(pod_type)
        self._topology = topology
        self._num_slices = num_slices
        self._chips_per_host = _chips_per_host_for_pod(pod_type)
        total_chips = num_chips_in_pod(pod_type)
        self._num_hosts = math.ceil(total_chips / self._chips_per_host)
        self._head_pgs: list = []
        self._slice_names: list = []
        self._pg: Optional[PlacementGroup] = None
        self._reserve(timeout)

    # -- reservation ---------------------------------------------------------

    def _reserve(self, timeout: float) -> None:
        import ray_tpu

        try:
            for _ in range(self._num_slices):
                head_pg = placement_group(
                    [{f"TPU-{self._pod_type}-head": 1}], strategy="STRICT_PACK"
                )
                self._head_pgs.append(head_pg)
                if not head_pg.wait(timeout):
                    raise TimeoutError(
                        f"could not reserve a {self._pod_type} slice head in "
                        f"{timeout}s (all slices busy or absent)"
                    )
            node_labels = {
                n["NodeID"]: n.get("Labels", {}) for n in ray_tpu.nodes()
            }
            for head_pg in self._head_pgs:
                from ray_tpu.util.placement_group import placement_group_table

                info = placement_group_table(head_pg)
                head_node = info["bundle_nodes"][0]
                name = node_labels.get(head_node, {}).get(
                    TPU_SLICE_NAME_LABEL
                )
                if not name:
                    raise RuntimeError(
                        f"head node {head_node} has no "
                        f"{TPU_SLICE_NAME_LABEL} label"
                    )
                self._slice_names.append(name)
            bundles = []
            selectors = []
            for name in self._slice_names:
                for _ in range(self._num_hosts):
                    bundles.append(dict(self.bundle_resources))
                    selectors.append({TPU_SLICE_NAME_LABEL: name})
            self._pg = placement_group(
                bundles,
                strategy="STRICT_SPREAD",
                bundle_label_selector=selectors,
            )
            if not self._pg.wait(timeout):
                raise TimeoutError(
                    f"slice bundles for {self._slice_names} not ready in "
                    f"{timeout}s"
                )
        except Exception:
            self.shutdown()
            raise

    # -- accessors -----------------------------------------------------------

    @property
    def placement_group(self) -> PlacementGroup:
        return self._pg

    @property
    def head_placement_groups(self) -> list:
        return list(self._head_pgs)

    @property
    def slice_names(self) -> list:
        return list(self._slice_names)

    @property
    def chips_per_host(self) -> int:
        return self._chips_per_host

    @property
    def num_hosts(self) -> int:
        return self._num_hosts

    @property
    def num_bundles(self) -> int:
        return self._num_hosts * self._num_slices

    @property
    def topology(self) -> Optional[str]:
        return self._topology

    @property
    def pod_type(self) -> str:
        return self._pod_type

    @property
    def accelerator_version(self) -> str:
        return self._accelerator_version

    @property
    def num_slices(self) -> int:
        return self._num_slices

    @property
    def bundle_resources(self) -> dict:
        return {"TPU": float(self._chips_per_host)}

    @property
    def bundle_label_selector(self) -> list:
        return [
            {TPU_SLICE_NAME_LABEL: name}
            for name in self._slice_names
            for _ in range(self._num_hosts)
        ]

    def shutdown(self) -> None:
        """Release the slice bundles and the head mutual-exclusion tokens."""
        if self._pg is not None:
            try:
                remove_placement_group(self._pg)
            except Exception:  # raylint: disable=RL006 -- pg remove during shutdown; GCS may already have dropped it
                pass
            self._pg = None
        for pg in self._head_pgs:
            try:
                remove_placement_group(pg)
            except Exception:  # raylint: disable=RL006 -- pg remove during shutdown; GCS may already have dropped it
                pass
        self._head_pgs = []


def slice_placement_group(
    topology: Optional[str] = None,
    accelerator_version: str = "v4",
    num_slices: int = 1,
    pod_type: Optional[str] = None,
    timeout: float = 100.0,
) -> SlicePlacementGroup:
    """Reserve ``num_slices`` whole slices (reference: util/tpu.py:458)."""
    return SlicePlacementGroup(
        topology=topology,
        accelerator_version=accelerator_version,
        num_slices=num_slices,
        pod_type=pod_type,
        timeout=timeout,
    )
