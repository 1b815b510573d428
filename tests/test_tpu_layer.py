"""TPU resource layer: topology math, accelerator manager env handling,
slice reservation (reference: python/ray/tests/accelerators/test_tpu.py,
python/ray/tests/test_tpu_slice_placement_groups.py)."""

import os
import time

import pytest

import ray_tpu
from ray_tpu.accelerators import detect_node_accelerators
from ray_tpu.accelerators.tpu import (
    TPU_SLICE_NAME_LABEL,
    TPU_TOPOLOGY_LABEL,
    TPU_WORKER_ID_LABEL,
    TPUAcceleratorManager,
    chips_per_host,
    num_chips_in_pod,
    num_hosts_in_pod,
    pod_type_from_topology,
    valid_pod_type,
)
from ray_tpu.util.placement_group import placement_group_table
from ray_tpu.util.testing import add_fake_tpu_slice
from ray_tpu.util.tpu import (
    SlicePlacementGroup,
    get_tpu_coordinator_env_vars,
    get_tpu_num_slices_for_workers,
    get_tpu_version_from_type,
    get_tpu_worker_resources,
)


# -- pure topology math ------------------------------------------------------


@pytest.mark.parametrize(
    "pod_type,chips,cph,hosts",
    [
        ("v4-8", 4, 4, 1),
        ("v4-16", 8, 4, 2),
        ("v4-32", 16, 4, 4),
        ("v5p-8", 4, 4, 1),
        ("v2-8", 4, 4, 1),
        ("v5litepod-4", 4, 4, 1),
        ("v5litepod-8", 8, 8, 1),
        ("v5litepod-16", 16, 8, 2),
        ("v6e-32", 32, 8, 4),
    ],
)
def test_pod_type_math(pod_type, chips, cph, hosts):
    assert num_chips_in_pod(pod_type) == chips
    assert chips_per_host(pod_type) == cph
    assert num_hosts_in_pod(pod_type) == hosts


def test_pod_type_from_topology():
    assert pod_type_from_topology("2x2x2", "v4") == "v4-16"
    assert pod_type_from_topology("4x4", "v6e") == "v6e-16"
    assert valid_pod_type("v4-16")
    assert not valid_pod_type("v9-16")
    assert not valid_pod_type("v4")
    assert get_tpu_version_from_type("TPU-V5P") == "v5p"
    assert get_tpu_version_from_type("v6e-8") == "v6e"


def test_worker_resources_math():
    n, res = get_tpu_worker_resources("2x2x2", "v4-16")
    assert n == 2 and res["TPU"] == 4 and res["CPU"] == 1
    n, res = get_tpu_worker_resources("2x2x2", "v4-16", num_slices=3)
    assert n == 6
    # Worker straddling a slice boundary is rejected.
    with pytest.raises(ValueError):
        get_tpu_worker_resources(
            "2x2x2", "v4-16", resources_per_unit={"TPU": 16}, num_slices=2
        )
    assert get_tpu_num_slices_for_workers("2x2x2", "v4-16", 5) == 3
    assert get_tpu_num_slices_for_workers("", "", 5) == 1


def test_coordinator_env_vars():
    env = get_tpu_coordinator_env_vars("10.0.0.1", 4, 2)
    assert env == {
        "MEGASCALE_COORDINATOR_ADDRESS": "10.0.0.1",
        "MEGASCALE_PORT": "8081",
        "MEGASCALE_NUM_SLICES": "4",
        "MEGASCALE_SLICE_ID": "2",
    }


# -- accelerator manager with simulated env ---------------------------------


def test_manager_env_detection(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v4-16")
    monkeypatch.setenv("TPU_NAME", "slice-a")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("TPU_TOPOLOGY", "2x2x2")
    m = TPUAcceleratorManager
    assert m.get_current_node_tpu_pod_type() == "v4-16"
    assert m.get_current_node_accelerator_type() == "TPU-V4"
    extra = m.get_current_node_additional_resources()
    assert extra == {"slice-a": 1.0, "TPU-v4-16-head": 1.0}
    labels = m.get_current_node_accelerator_labels()
    assert labels[TPU_SLICE_NAME_LABEL] == "slice-a"
    assert labels[TPU_WORKER_ID_LABEL] == "0"
    # Worker 1 gets no head resource.
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    assert "TPU-v4-16-head" not in m.get_current_node_additional_resources()


def test_manager_pod_type_from_topology_env(monkeypatch):
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    monkeypatch.setenv("TPU_TOPOLOGY", "4x4")
    assert TPUAcceleratorManager.get_current_node_tpu_pod_type() == "v4-32"


def test_visible_chips_injection(monkeypatch):
    for var in (
        "TPU_VISIBLE_CHIPS",
        "TPU_CHIPS_PER_HOST_BOUNDS",
        "TPU_HOST_BOUNDS",
    ):
        monkeypatch.delenv(var, raising=False)
    m = TPUAcceleratorManager
    m.set_current_process_visible_accelerator_ids(["0", "1"])
    assert os.environ["TPU_VISIBLE_CHIPS"] == "0,1"
    assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    assert os.environ["TPU_HOST_BOUNDS"] == "1,1,1"
    assert m.get_current_process_visible_accelerator_ids() == ["0", "1"]


def test_visible_chips_pin_the_jax_platform(monkeypatch):
    for var in ("TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "stale")
    m = TPUAcceleratorManager
    # Owning chips asks for the TPU by name (a chip that cannot be opened is
    # then an error, not a CPU run) ...
    monkeypatch.delenv("JAX_PLATFORMS")
    m.set_current_process_visible_accelerator_ids([2])
    assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"
    # ... unless the platform was pinned from outside, as the tests do.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    m.set_current_process_visible_accelerator_ids([2])
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    # Owning none keeps the process off the TPU whatever was inherited.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    m.set_current_process_visible_accelerator_ids([])
    assert os.environ["TPU_VISIBLE_CHIPS"] == ""
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert m.get_current_process_visible_accelerator_ids() == []


def test_validate_request_quantity():
    ok, _ = TPUAcceleratorManager.validate_resource_request_quantity(4)
    assert ok
    ok, msg = TPUAcceleratorManager.validate_resource_request_quantity(3)
    assert not ok and "3" in msg
    ok, _ = TPUAcceleratorManager.validate_resource_request_quantity(0.5)
    assert not ok


def test_detect_node_accelerators_off_tpu(monkeypatch):
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.setattr(
        TPUAcceleratorManager, "get_current_node_num_accelerators", lambda: 0
    )
    resources, labels = detect_node_accelerators()
    assert resources == {} and labels == {}


def test_init_defaults_advertise_detected_chips(monkeypatch):
    from ray_tpu.core.api import _default_labels, _default_resources

    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    for var in ("TPU_ACCELERATOR_TYPE", "TPU_NAME", "TPU_WORKER_ID"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("TPU_TOPOLOGY", "2x2")
    monkeypatch.setattr(
        TPUAcceleratorManager, "get_current_node_num_accelerators", lambda: 4
    )
    # A bare TPU VM: the chip count is all that is certain.
    assert _default_resources(2.0)["TPU"] == 4.0
    assert _default_labels()[TPU_TOPOLOGY_LABEL] == "2x2"

    # On a host that has chips a detection error stops the node.
    def broken():
        raise RuntimeError("unreadable slice identity")

    monkeypatch.setattr(
        TPUAcceleratorManager, "get_current_node_accelerator_labels", broken
    )
    with pytest.raises(RuntimeError, match="slice identity"):
        _default_resources(2.0)


# -- TPU leases own their chips (fake 4-chip node) ----------------------------


@pytest.fixture
def four_chip_node():
    runtime = ray_tpu.init(num_cpus=4, resources={"TPU": 4.0})
    yield runtime
    ray_tpu.shutdown()


def _scope_fn():
    # Nested so that it travels to the workers by value.
    def scope():
        return {
            "pid": os.getpid(),
            "chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "bounds": os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS"),
            "platform": os.environ.get("JAX_PLATFORMS"),
        }

    return scope


def test_tpu_actors_own_disjoint_chips(four_chip_node):
    @ray_tpu.remote
    class Scoped:
        scope = staticmethod(_scope_fn())

    a = Scoped.options(num_tpus=1).remote()
    b = Scoped.options(num_tpus=1).remote()
    plain = Scoped.remote()
    sa, sb, sp = ray_tpu.get(
        [a.scope.remote(), b.scope.remote(), plain.scope.remote()], timeout=60
    )
    assert {sa["chips"], sb["chips"]} == {"0", "1"}
    assert sa["bounds"] == sb["bounds"] == "1,1,1"
    # No TPU in the lease: no chip, and no way to open one.
    assert sp["chips"] == "" and sp["platform"] == "cpu"
    assert len({sa["pid"], sb["pid"], sp["pid"]}) == 3


def test_tpu_worker_is_reused_only_for_its_own_chips(four_chip_node):
    scope = ray_tpu.remote(_scope_fn())
    first = ray_tpu.get(scope.options(num_tpus=1).remote(), timeout=60)
    assert first["chips"] == "0"
    # The returned worker still holds chip 0 in libtpu: a lease without a TPU
    # does not land in it ...
    plain = ray_tpu.get(scope.remote(), timeout=60)
    assert plain["pid"] != first["pid"] and plain["chips"] == ""
    # ... the same chip comes back to the same process ...
    again = ray_tpu.get(scope.options(num_tpus=1).remote(), timeout=60)
    assert (again["pid"], again["chips"]) == (first["pid"], "0")
    # ... and a lease that needs chip 0 in another set retires it first.
    pair = ray_tpu.get(scope.options(num_tpus=2).remote(), timeout=60)
    assert pair["chips"] == "0,1" and pair["bounds"] == "1,2,1"
    assert pair["pid"] != first["pid"]
    with pytest.raises(OSError):
        os.kill(first["pid"], 0)


# -- slice reservation on a fake multi-slice cluster -------------------------


@pytest.fixture(scope="module")
def tpu_cluster():
    runtime = ray_tpu.init(num_cpus=2)
    add_fake_tpu_slice(runtime, "v4-16", "slice-a")
    add_fake_tpu_slice(runtime, "v4-16", "slice-b")
    time.sleep(1.0)
    yield runtime
    ray_tpu.shutdown()


def test_slice_reservation_single(tpu_cluster):
    spg = SlicePlacementGroup(pod_type="v4-16", timeout=30)
    try:
        assert spg.num_hosts == 2 and spg.chips_per_host == 4
        assert spg.slice_names[0] in ("slice-a", "slice-b")
        info = placement_group_table(spg.placement_group)
        assert info["state"] == "CREATED"
        # Both bundles on distinct hosts of the same slice.
        assert len(set(info["bundle_nodes"])) == 2
        node_labels = {
            n["NodeID"]: n["Labels"] for n in ray_tpu.nodes()
        }
        for nid in info["bundle_nodes"]:
            assert (
                node_labels[nid][TPU_SLICE_NAME_LABEL] == spg.slice_names[0]
            )
    finally:
        spg.shutdown()


def test_slice_reservation_two_slices_exclusive(tpu_cluster):
    spg = SlicePlacementGroup(pod_type="v4-16", num_slices=2, timeout=30)
    try:
        assert sorted(spg.slice_names) == ["slice-a", "slice-b"]
        assert spg.num_bundles == 4
        # A third reservation must fail: both heads are taken.
        with pytest.raises(TimeoutError):
            SlicePlacementGroup(pod_type="v4-16", timeout=3)
    finally:
        spg.shutdown()
    # After shutdown the heads are free again.
    spg2 = SlicePlacementGroup(pod_type="v4-16", timeout=30)
    spg2.shutdown()


def test_slice_reservation_by_topology(tpu_cluster):
    spg = SlicePlacementGroup(topology="2x2x2", accelerator_version="v4")
    try:
        assert spg.pod_type == "v4-16"
    finally:
        spg.shutdown()
