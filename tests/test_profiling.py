"""Live profiling: sampled stacks, thread dumps, jax trace capture
(reference: dashboard/modules/reporter/profile_manager.py:78; plus the
TPU-side jax.profiler capture SURVEY 5.1 names)."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import profiling, state

pytestmark = pytest.mark.timeout(180)


@pytest.fixture(scope="module")
def cluster():
    runtime = ray_tpu.init(num_cpus=4)
    yield runtime
    ray_tpu.shutdown()


def test_in_process_sampler_catches_busy_function():
    import threading

    stop = threading.Event()

    def busy_beaver():
        while not stop.is_set():
            sum(range(2000))

    t = threading.Thread(target=busy_beaver, name="beaver", daemon=True)
    t.start()
    try:
        prof = profiling.sample_collapsed_stacks(
            duration_s=0.6, interval_s=0.005
        )
    finally:
        stop.set()
        t.join()
    assert prof["samples"] > 10
    assert any("busy_beaver" in stack for stack in prof["stacks"]), list(
        prof["stacks"]
    )[:5]


def test_stack_dump_lists_threads():
    dump = profiling.collect_stack_dump()
    assert "Thread MainThread" in dump
    assert "collect_stack_dump" in dump


def test_profile_remote_worker(cluster):
    @ray_tpu.remote
    class Spinner:
        def __init__(self):
            import threading

            self._stop = threading.Event()

            def grind():
                while not self._stop.is_set():
                    sum(range(5000))

            threading.Thread(target=grind, daemon=True).start()

        def my_id(self):
            import ray_tpu as rr

            return rr.get_runtime_context().worker_id

        def halt(self):
            self._stop.set()

    s = Spinner.remote()
    worker_id = ray_tpu.get(s.my_id.remote(), timeout=60)

    workers = [w for w in state.list_workers() if "worker_id" in w]
    assert any(w["worker_id"] == worker_id for w in workers)

    prof = state.profile_worker(worker_id, duration_s=0.8)
    assert prof["samples"] > 5
    assert any("grind" in stack for stack in prof["stacks"]), list(
        prof["stacks"]
    )[:5]

    dump = state.dump_worker_stacks(worker_id)
    assert "grind" in dump
    ray_tpu.get(s.halt.remote(), timeout=30)
    ray_tpu.kill(s)


def test_jax_trace_capture(cluster, tmp_path):
    import glob
    import os
    import threading

    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.ones((128, 128))
    f(x).block_until_ready()  # compile outside the capture window

    def burn():
        for _ in range(50):
            f(x).block_until_ready()
            time.sleep(0.005)

    # Device work must run DURING the capture window to land in the trace.
    t = threading.Thread(target=burn, daemon=True)
    t.start()
    out = profiling.capture_jax_trace(str(tmp_path / "trace"), 0.5)
    t.join()
    assert out["trace_dir"] == str(tmp_path / "trace")
    assert os.path.isdir(out["trace_dir"])
    # A real (non-empty) xplane capture was written.
    artifacts = glob.glob(
        os.path.join(out["trace_dir"], "**", "*.xplane.pb"), recursive=True
    ) + glob.glob(
        os.path.join(out["trace_dir"], "**", "*.trace.json.gz"),
        recursive=True,
    )
    assert artifacts, os.listdir(out["trace_dir"])
    assert any(os.path.getsize(a) > 0 for a in artifacts)


def test_jax_trace_carries_a_clock_anchor_and_the_flight_recorder(tmp_path):
    """An operator's trace can be laid against the program's spans: the
    capture writes a host event at a wall time it returns, and saves this
    process's flight-recorder rings beside the trace."""
    import os

    from jax.profiler import ProfileData

    from ray_tpu.util import flightrec

    flightrec.record("llm", "llm.decode_step", dur_s=0.04, batch=3)
    t0 = time.time_ns()
    out = profiling.capture_jax_trace(str(tmp_path / "trace"), 0.2)
    assert t0 <= out["anchor_wall_ns"] <= time.time_ns()
    with open(out["flightrec_snapshot"]) as f:
        snap = json.load(f)
    assert os.path.dirname(out["flightrec_snapshot"]) == out["trace_dir"]
    assert snap["anchor_wall_ns"] == out["anchor_wall_ns"]
    assert snap["clock_anchor"] == profiling.CLOCK_ANCHOR
    assert {"mono_anchor", "wall_anchor"} <= set(snap)
    assert any(
        e["phase"] == "llm.decode_step" and e["extra"] == {"batch": 3}
        for e in snap["rings"]["llm"]["events"]
    )
    (xplane,) = [
        os.path.join(d, f) for d, _, files in os.walk(out["trace_dir"])
        for f in files if f.endswith(".xplane.pb")
    ]
    anchors = [
        e for plane in ProfileData.from_file(xplane).planes
        for line in plane.lines for e in line.events
        if e.name == profiling.CLOCK_ANCHOR
    ]
    assert len(anchors) == 1 and anchors[0].duration_ns >= 1_000_000


def test_dashboard_profile_routes(cluster):
    from ray_tpu.dashboard import DashboardHead

    dash = DashboardHead(host="127.0.0.1", port=0)
    port = dash.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/profile/dump?worker_id=driver",
            timeout=60,
        ) as r:
            out = json.loads(r.read())
        assert "MainThread" in out["stacks"]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/profile"
            f"?worker_id=driver&duration=0.5",
            timeout=60,
        ) as r:
            out = json.loads(r.read())
        assert out["samples"] > 0
    finally:
        dash.stop()

def test_dashboard_ui_page(cluster):
    """The root path serves the self-contained HTML UI (the reference's
    React frontend role, dependency-free)."""
    from ray_tpu.dashboard import DashboardHead

    dash = DashboardHead(host="127.0.0.1", port=0)
    port = dash.start()
    try:
        req = urllib.request.Request(f"http://127.0.0.1:{port}/")
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers.get("Content-Type", "").startswith("text/html")
            page = r.read().decode()
        assert "ray_tpu cluster" in page and "/api/nodes" in page
    finally:
        dash.stop()
