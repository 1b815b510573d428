"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax imports.

Mirrors the reference's strategy of testing distributed behavior without the
real hardware (reference: python/ray/tests/conftest.py:596 starts multi-raylet
local clusters; accelerator tests mock device discovery). Here a virtual
8-device CPU mesh stands in for a TPU slice so every sharding/collective path
compiles and runs in CI.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take a chip
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

import signal  # noqa: E402

import pytest  # noqa: E402

# -- per-test timeout (pytest-timeout is not in the image) --------------------
# The reference pins a global per-test timeout in pytest.ini (SURVEY.md §4) so
# one hung test cannot wedge CI forever. Same contract here via SIGALRM: each
# phase (setup/call/teardown) gets the allotment and a clean TimeoutError on
# overrun, so the suite keeps going. Override per test with
# @pytest.mark.timeout(N) or globally with RAY_TPU_TEST_TIMEOUT.

DEFAULT_TEST_TIMEOUT_S = int(os.environ.get("RAY_TPU_TEST_TIMEOUT", "180"))


def _phase_timeout_s(item) -> int:
    marker = item.get_closest_marker("timeout")
    if marker and marker.args:
        return int(marker.args[0])
    return DEFAULT_TEST_TIMEOUT_S


def _timed_phase(item, phase):
    seconds = _phase_timeout_s(item)

    def _on_alarm(signum, frame):  # noqa: ARG001
        raise TimeoutError(
            f"{item.nodeid} {phase} exceeded {seconds}s "
            f"(override: @pytest.mark.timeout(N) / RAY_TPU_TEST_TIMEOUT)"
        )

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    yield from _timed_phase(item, "setup")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    yield from _timed_phase(item, "call")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item, nextitem):  # noqa: ARG001
    yield from _timed_phase(item, "teardown")


# -- leftover-process reaper --------------------------------------------------
# Cluster fixtures kill their worker trees in ray_tpu.shutdown(); this is the
# backstop for anything that escapes (a hung teardown, a test that crashed
# mid-cluster). A stray worker once ate this 1-core box for 5+ hours through a
# driver gate window — never again.


def _descendant_pids(root_pid: int) -> list[int]:
    children: dict[int, list[int]] = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                # field 4 (after the parenthesised, possibly-spacey comm)
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
        children.setdefault(ppid, []).append(int(entry))
    out: list[int] = []
    stack = [root_pid]
    while stack:
        for child in children.get(stack.pop(), []):
            out.append(child)
            stack.append(child)
    return out


@pytest.fixture(autouse=True, scope="module")
def _reap_leftover_children():
    """Autouse + module scope = instantiated before any module cluster
    fixture, finalized after them: whatever their teardown leaves alive
    gets SIGKILLed here so it cannot leak into the next module (or outlive
    the suite)."""
    yield
    leftovers = _descendant_pids(os.getpid())
    for pid in leftovers:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            continue
        print(f"[conftest] SIGKILLed leftover child pid={pid}", flush=True)


# -- smoke tier ---------------------------------------------------------------
# `pytest -m smoke` = the < 2-minute-on-one-core confidence set. Applied by
# module so the list lives in one place instead of scattered marks.

SMOKE_MODULES = {
    "test_core_runtime",
    "test_memory_and_sync",
    "test_util_pool_queue",
    "test_observability",
    "test_tracing",
    "test_runtime_env",
}


def pytest_collection_modifyitems(config, items):  # noqa: ARG001
    for item in items:
        if item.fspath.purebasename in SMOKE_MODULES:
            item.add_marker(pytest.mark.smoke)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


# -- condition polling --------------------------------------------------------
# THE wait helper for distributed assertions: poll a predicate instead of a
# fixed sleep (fixed sleeps are exactly long enough to flake on a loaded
# box and exactly short enough to waste time on an idle one). Returns the
# predicate's first truthy value so callers can assert on it.


def wait_for_condition(pred, timeout: float = 20.0, interval: float = 0.05):
    import time as _time

    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        _time.sleep(interval)
    raise TimeoutError(f"condition not met within {timeout}s: {pred}")


@pytest.fixture
def wait_for():
    return wait_for_condition


def add_node_and_wait(runtime, wait_for, resources):
    """Add a node and poll until THIS node's id shows alive in the head's
    gossiped view (a fixed post-add sleep flakes both ways on a loaded
    box; matching on a resource marker instead of the id can be satisfied
    by a just-killed node's stale still-alive view in the
    kill-then-re-add pattern)."""
    node = runtime.add_node(dict(resources))
    wait_for(
        lambda: (
            (v := runtime.head.cluster_view.get(node.node_id)) is not None
            and v.alive
        ),
        timeout=30.0,
    )
    return node
