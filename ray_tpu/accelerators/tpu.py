"""TPU accelerator manager: chip detection, topology math, slice identity.

Reference parity: python/ray/_private/accelerators/tpu.py (683 LoC) —
chip autodetect via /dev/accel*|/dev/vfio (:305–324), TPU_VISIBLE_CHIPS +
host-bounds env injection (:388–428), pod-type/topology/worker-id from GKE
env or GCE metadata (:431–538), per-node extra resources
``{tpu_name: 1, "TPU-<pod>-head": 1}`` (:587–650), node labels
``ray.io/tpu-{slice-name,worker-id,topology,pod-type}`` (:652–683).

TPU-first design notes: identity comes from env (GKE injects
TPU_ACCELERATOR_TYPE / TPU_TOPOLOGY / TPU_WORKER_ID / TPU_NAME); on bare GCE
the metadata server would fill the same fields — that fetch is a pluggable
hook (`_metadata_lookup`) so tests and airgapped runs can stub it. All
topology math (chips per host, host count) is pure and unit-tested.
"""

from __future__ import annotations

import glob
import logging
import math
import os
from typing import Optional

from ray_tpu.accelerators.accelerator import AcceleratorManager

logger = logging.getLogger(__name__)

# -- env vars (GKE-compatible names so existing TPU pods work unchanged) -----
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v4-16"
TPU_TOPOLOGY_ENV = "TPU_TOPOLOGY"  # e.g. "2x2x2"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"  # 0-based host index in the slice
TPU_NAME_ENV = "TPU_NAME"  # slice name, unique per slice
TPU_WORKER_HOSTNAMES_ENV = "TPU_WORKER_HOSTNAMES"  # comma list, GKE

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
JAX_PLATFORMS_ENV = "JAX_PLATFORMS"
NOSET_TPU_VISIBLE_CHIPS_ENV = "RAY_TPU_NOSET_TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
# Physical chip-grid bounds for sub-host visibility (4-chip hosts are a
# 2x2 grid; exposing 1 or 2 chips needs matching bounds).
_CHIPS_PER_HOST_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}
_SINGLE_HOST_BOUNDS = "1,1,1"

# -- node label keys ---------------------------------------------------------
TPU_SLICE_NAME_LABEL = "ray.io/tpu-slice-name"
TPU_WORKER_ID_LABEL = "ray.io/tpu-worker-id"
TPU_TOPOLOGY_LABEL = "ray.io/tpu-topology"
TPU_POD_TYPE_LABEL = "ray.io/tpu-pod-type"

# Generations with 1 TensorCore per chip and 8-chip hosts; all others have
# 2 cores per chip and 4-chip hosts. Pod-type numbers count cores for
# 2-core generations (v4-16 = 16 cores = 8 chips) and chips for 1-core
# generations (v5litepod-16 = 16 chips).
_ONE_CORE_8_CHIP_GENERATIONS = ("v5litepod", "v6e")
_DEFAULT_CHIPS_PER_HOST = 4
_MAX_CHIPS_PER_HOST = 8

_VALID_GENERATIONS = (
    "v2",
    "v3",
    "v4",
    "v5p",
    "v5litepod",
    "v6e",
)


# -- pure topology math ------------------------------------------------------


def tpu_generation(pod_type: str) -> str:
    """``"v4-16"`` → ``"v4"`` (raises on malformed pod types)."""
    gen = pod_type.split("-")[0]
    if gen not in _VALID_GENERATIONS:
        raise ValueError(
            f"invalid TPU pod type {pod_type!r}; generation must be one of "
            f"{_VALID_GENERATIONS}"
        )
    return gen


def cores_per_chip(generation: str) -> int:
    return 1 if generation in _ONE_CORE_8_CHIP_GENERATIONS else 2


def num_chips_in_pod(pod_type: str) -> int:
    """Total chips in a slice of ``pod_type`` (``"v4-16"`` → 8)."""
    gen = tpu_generation(pod_type)
    count = int(pod_type.split("-")[1])
    return count // cores_per_chip(gen)


def chips_per_host(pod_type: str) -> int:
    """Chips each host contributes: 8 for v5e/v6e (or the whole slice when
    smaller than a host), else 4 (partial hosts keep their chip count)."""
    gen = tpu_generation(pod_type)
    total = num_chips_in_pod(pod_type)
    cap = (
        _MAX_CHIPS_PER_HOST
        if gen in _ONE_CORE_8_CHIP_GENERATIONS
        else _DEFAULT_CHIPS_PER_HOST
    )
    return min(total, cap)


def num_hosts_in_pod(pod_type: str) -> int:
    return math.ceil(num_chips_in_pod(pod_type) / chips_per_host(pod_type))


def num_chips_from_topology(topology: str) -> int:
    """``"2x2x2"`` → 8."""
    total = 1
    for dim in topology.split("x"):
        total *= int(dim)
    return total


def pod_type_from_topology(topology: str, generation: str) -> str:
    """Infer ``v4-16``-style pod type from a topology and generation."""
    chips = num_chips_from_topology(topology)
    count = chips * cores_per_chip(generation)
    return f"{generation}-{count}"


def valid_pod_type(pod_type: str) -> bool:
    try:
        parts = pod_type.split("-")
        return (
            len(parts) == 2
            and parts[0] in _VALID_GENERATIONS
            and int(parts[1]) > 0
        )
    except (ValueError, IndexError):
        return False


# -- metadata hooks ----------------------------------------------------------
# On bare GCE the instance metadata server supplies accelerator-type /
# agent-worker-number / instance-id; tests and airgapped runs override this.

_metadata_lookup = None  # Optional[Callable[[str], Optional[str]]]


def set_metadata_lookup(fn) -> None:
    global _metadata_lookup
    _metadata_lookup = fn


def _metadata(key: str) -> Optional[str]:
    if _metadata_lookup is not None:
        try:
            return _metadata_lookup(key)
        except Exception:  # raylint: disable=RL006 -- GCE metadata server absent off-cloud; None routes callers to env/defaults
            return None
    return None


class TPUAcceleratorManager(AcceleratorManager):
    """TPU node bootstrap: detection, env scoping, resources, labels."""

    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Count chips via accelerator device files (vfio on newer stacks,
        accel on older); 0 off-TPU."""
        try:
            vfio = [
                p
                for p in glob.glob("/dev/vfio/*")
                if os.path.basename(p).isdigit()
            ]
            if vfio:
                return len(vfio)
            return len(glob.glob("/dev/accel*"))
        except Exception:  # raylint: disable=RL006 -- accelerator device-file probe; unreadable /dev means 0 local chips
            return 0

    @staticmethod
    def get_current_node_tpu_pod_type() -> Optional[str]:
        """Slice pod type (``v4-16``): env, else derived from topology env,
        else metadata server."""
        pod_type = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
        if not pod_type:
            pod_type = _metadata("accelerator-type")
        if pod_type and valid_pod_type(pod_type):
            return pod_type
        topology = os.environ.get(TPU_TOPOLOGY_ENV)
        if topology:
            # GKE v5e/v6e style: topology + accelerator family from the
            # pod type env even when malformed, default to v4.
            gen = (pod_type or "v4").split("-")[0]
            if gen in _VALID_GENERATIONS:
                try:
                    return pod_type_from_topology(topology, gen)
                except ValueError:
                    return None
        return None

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if not pod_type:
            return None
        return "TPU-" + tpu_generation(pod_type).upper()

    @staticmethod
    def get_current_node_tpu_name() -> Optional[str]:
        return os.environ.get(TPU_NAME_ENV) or _metadata("instance-id")

    @staticmethod
    def get_current_node_tpu_worker_id() -> Optional[int]:
        raw = os.environ.get(TPU_WORKER_ID_ENV)
        if raw is None:
            raw = _metadata("agent-worker-number")
        try:
            return int(raw) if raw is not None else None
        except ValueError:
            return None

    @staticmethod
    def get_current_node_tpu_topology() -> Optional[str]:
        return os.environ.get(TPU_TOPOLOGY_ENV) or _metadata("topology")

    @staticmethod
    def get_current_process_visible_accelerator_ids() -> Optional[list]:
        raw = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if raw is None:
            return None
        return [] if raw == "" else raw.split(",")

    @staticmethod
    def set_current_process_visible_accelerator_ids(ids: list) -> None:
        """Scope this process (and its JAX runtime) to ``ids`` chips. Call
        it before jax is imported: jax reads JAX_PLATFORMS once.

        Sub-host visibility needs TPU_CHIPS_PER_HOST_BOUNDS +
        TPU_HOST_BOUNDS alongside TPU_VISIBLE_CHIPS so libtpu carves the
        chip grid correctly (reference: tpu.py:388–428). Measured on a v5e
        2x2 host (libtpu 0.0.34): four one-chip processes and two two-chip
        processes (chips 0,1 and 2,3) initialise side by side with these
        bounds; TPU_VISIBLE_CHIPS alone fails on libtpu's lockfile.
        """
        if os.environ.get(NOSET_TPU_VISIBLE_CHIPS_ENV):
            return
        ids = [str(i) for i in ids]
        os.environ[TPU_VISIBLE_CHIPS_ENV] = ",".join(ids)
        if not ids:
            # libtpu reads an empty list as "every chip", so a process that
            # owns none is kept off the TPU platform altogether.
            os.environ[JAX_PLATFORMS_ENV] = "cpu"
            return
        # A process that owns chips asks for them by name: a chip it cannot
        # open is then an error here, never a silent CPU run. A platform
        # pinned from outside (tests pin cpu) stands.
        os.environ.setdefault(JAX_PLATFORMS_ENV, "tpu,cpu")
        n = len(ids)
        bounds = _CHIPS_PER_HOST_BOUNDS.get(n)
        if bounds is not None and n < _DEFAULT_CHIPS_PER_HOST:
            os.environ[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds
            os.environ[TPU_HOST_BOUNDS_ENV] = _SINGLE_HOST_BOUNDS

    @staticmethod
    def get_current_node_additional_resources() -> Optional[dict]:
        """``{<slice-name>: 1}`` on every slice host plus
        ``{"TPU-<pod>-head": 1}`` on worker 0 — the targetable coordinator
        that SlicePlacementGroup grabs first (reference: tpu.py:587–650)."""
        name = TPUAcceleratorManager.get_current_node_tpu_name()
        worker_id = TPUAcceleratorManager.get_current_node_tpu_worker_id()
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if not (name and worker_id is not None and pod_type):
            return None
        resources = {name: 1.0}
        if worker_id == 0:
            resources[f"TPU-{pod_type}-head"] = 1.0
        return resources

    @staticmethod
    def get_current_node_accelerator_labels() -> dict:
        labels = {}
        name = TPUAcceleratorManager.get_current_node_tpu_name()
        if name:
            labels[TPU_SLICE_NAME_LABEL] = name
        worker_id = TPUAcceleratorManager.get_current_node_tpu_worker_id()
        if worker_id is not None:
            labels[TPU_WORKER_ID_LABEL] = str(worker_id)
        topology = TPUAcceleratorManager.get_current_node_tpu_topology()
        if topology:
            labels[TPU_TOPOLOGY_LABEL] = topology
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if pod_type:
            labels[TPU_POD_TYPE_LABEL] = pod_type
        return labels

    @staticmethod
    def validate_resource_request_quantity(quantity: float):
        """TPU requests must be whole chips in {1, 2, 4} or multiples of a
        full host — fractional or odd chip counts can't map onto the chip
        grid (reference: tpu.py:374)."""
        if quantity != int(quantity):
            return False, "TPU chip requests must be whole numbers"
        q = int(quantity)
        if q in (1, 2, 4) or (q > 4 and q % 4 == 0) or q == 8:
            return True, None
        return (
            False,
            f"cannot request {q} TPU chips: valid counts are 1, 2, 4, or "
            "whole hosts (multiples of 4, or 8 on v5e/v6e)",
        )
