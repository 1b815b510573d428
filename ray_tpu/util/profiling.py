"""Live profiling: sampled Python stacks + JAX/XLA trace capture.

Reference parity: python/ray/dashboard/modules/reporter/profile_manager.py:78
(py-spy CPU profiles / stack dumps per process, triggered from the
dashboard). Redesign: py-spy is not in the image and needs ptrace
privileges; since every runtime process already serves RPCs, profiling is
IN-PROCESS — a pure-Python wall-clock sampler over ``sys._current_frames``
(flamegraph-ready collapsed stacks) and an instant all-threads dump. The
TPU half (SURVEY §5.1): ``jax.profiler`` trace capture on any worker,
written under the session dir for TensorBoard/XProf — the device-side
timeline the reference has no equivalent of.

Driver surface (ray_tpu.util.state also re-exports these):
    profiling.profile_worker(worker_id, duration_s=5)     -> collapsed stacks
    profiling.dump_worker_stacks(worker_id)               -> thread dump text
    profiling.capture_worker_jax_trace(worker_id, dur_s)  -> trace dir path
(``capture_jax_trace(trace_dir, duration_s)`` is the LOCAL primitive the
worker handler runs; the remote form is capture_worker_jax_trace.)
Dashboard: GET /api/profile?worker_id=..&duration=..,
           GET /api/profile/dump?worker_id=..,
           POST /api/profile/jax_trace?worker_id=..&duration=..
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from collections import Counter


def collect_stack_dump() -> str:
    """One formatted snapshot of every thread's Python stack (the
    'py-spy dump' role)."""
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in frames.items():
        out.append(
            f"Thread {names.get(ident, '?')} (ident={ident}):\n"
            + "".join(traceback.format_stack(frame))
        )
    return "\n".join(out)


def sample_collapsed_stacks(
    duration_s: float = 5.0,
    interval_s: float = 0.01,
    exclude_idle: bool = True,
    tag_spans: bool = True,
) -> dict:
    """Wall-clock sampling profile of THIS process: collapsed stacks
    ('frame;frame;...' -> sample count, the flamegraph input format).
    Run from a non-sampled thread (callers use an executor thread).

    With ``tag_spans`` (default), a sample taken while its thread is
    inside a live tracing span gets a synthetic root frame
    ``span:<trace_id>/<span_id>`` — so collapsed stacks can be filtered
    to one slow request's trace id."""
    from ray_tpu.util import tracing

    me = threading.get_ident()
    counts: Counter = Counter()
    samples = 0
    # Leaf functions that mean "parked", matched on the EXACT co_name (a
    # substring match would misclassify e.g. selection_sort as idle).
    idle_leaves = {
        "wait",
        "select",
        "poll",
        "epoll",
        "accept",
        "recv",
        "recv_into",
        "read",
        "readinto",
        "_wait_for_tstate_lock",
        "sleep",
    }
    deadline = time.monotonic() + duration_s
    while time.monotonic() < deadline:
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            stack = []
            leaf_name = frame.f_code.co_name
            f = frame
            while f is not None:
                code = f.f_code
                stack.append(f"{code.co_name} ({code.co_filename}:{f.f_lineno})")
                f = f.f_back
            if exclude_idle and leaf_name in idle_leaves:
                # Parked threads (executor waiters, selectors) dominate
                # otherwise; the CPU story is in the rest.
                continue
            key = ";".join(reversed(stack))
            if tag_spans:
                span = tracing.active_span_for_thread(ident)
                if span is not None:
                    key = f"span:{span[0]}/{span[1]};{key}"
            counts[key] += 1
        samples += 1
        time.sleep(interval_s)
    return {
        "duration_s": duration_s,
        "interval_s": interval_s,
        "samples": samples,
        "stacks": {
            k: v for k, v in counts.most_common() if v > 0
        },
    }


# The host event capture_jax_trace writes at a wall time it knows, so that
# the trace's clock can be laid on the wall clock (and with it on the
# flight recorder's spans, which carry a wall anchor of their own).
CLOCK_ANCHOR = "raytpu_clock_anchor"
FLIGHTREC_SNAPSHOT = "flightrec_snapshot.json"


def capture_jax_trace(trace_dir: str, duration_s: float = 3.0) -> dict:
    """Capture a jax.profiler (XLA/XPlane) trace of THIS process for
    ``duration_s`` — device ops included when a TPU is attached. The
    output dir loads in TensorBoard's profile plugin / XProf.

    So that the trace can be laid against the program's own spans, the
    capture opens with a host annotation named ``CLOCK_ANCHOR`` whose wall
    time is returned as ``anchor_wall_ns`` (wall = trace time + the
    difference at that event), and closes by saving
    ``flightrec.snapshot()`` as ``FLIGHTREC_SNAPSHOT`` in ``trace_dir``:
    every ring of this process, with the wall anchor its monotonic times
    are read against. The anchor is only the first guess: the profiler's
    laying of the device's plane against the host's errs by up to 2 ms.
    The snapshot's ``llm.prefill`` / ``llm.decode_step`` events carry
    ``seq`` (and ``next_seq``), the number of the launch, and the engine's
    programs run in launch order, so the trace's ``jit_paged_prefill`` /
    ``jit_paged_decode`` runs in start order are consecutive numbers: an
    event finds its run by counting, whatever the clocks say."""
    import json
    import os

    import jax

    from ray_tpu.util import flightrec

    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(CLOCK_ANCHOR):
            anchor_wall_ns = time.time_ns()
            time.sleep(0.001)
        time.sleep(duration_s)
    finally:
        jax.profiler.stop_trace()
    snapshot_path = os.path.join(trace_dir, FLIGHTREC_SNAPSHOT)
    snap = flightrec.snapshot()
    snap["anchor_wall_ns"] = anchor_wall_ns
    snap["clock_anchor"] = CLOCK_ANCHOR
    with open(snapshot_path, "w") as f:
        json.dump(snap, f, separators=(",", ":"), default=str)
    return {
        "trace_dir": trace_dir,
        "duration_s": duration_s,
        "anchor_wall_ns": anchor_wall_ns,
        "flightrec_snapshot": snapshot_path,
    }


# -- driver-side helpers ------------------------------------------------------


def _worker_addr(worker_id: str) -> tuple:
    """Resolve a worker's RPC address via the nodes' worker tables
    (reference: the dashboard agent resolving a pid; here worker ids are
    cluster-wide)."""
    from ray_tpu.core import api as core_api

    w = core_api._require_worker()
    if worker_id in ("driver", w.worker_id):
        return tuple(w.endpoint.address)
    import ray_tpu

    for node in ray_tpu.nodes():
        if not node.get("Alive", True):
            continue
        try:
            info = w.endpoint.call(
                tuple(node["Address"]), "node.get_info", {}, timeout=5
            )
        except Exception:  # raylint: disable=RL006 -- per-node info probe; unreachable nodes are skipped
            continue
        for rec in info.get("workers", []):
            if rec.get("worker_id") == worker_id and rec.get("addr"):
                return tuple(rec["addr"])
    raise ValueError(f"no live worker {worker_id!r} in the cluster")


def profile_worker(
    worker_id: str, duration_s: float = 5.0, interval_s: float = 0.01
) -> dict:
    """Sampled CPU profile of any live worker (or "driver" for this
    process)."""
    from ray_tpu.core import api as core_api

    w = core_api._require_worker()
    return w.endpoint.call(
        _worker_addr(worker_id),
        "worker.profile",
        {"duration_s": duration_s, "interval_s": interval_s},
        timeout=duration_s + 30,
    )


def dump_worker_stacks(worker_id: str) -> str:
    from ray_tpu.core import api as core_api

    w = core_api._require_worker()
    return w.endpoint.call(
        _worker_addr(worker_id), "worker.dump_stacks", {}, timeout=30
    )


def capture_worker_jax_trace(
    worker_id: str, duration_s: float = 3.0, trace_dir: str | None = None
) -> dict:
    from ray_tpu.core import api as core_api

    w = core_api._require_worker()
    return w.endpoint.call(
        _worker_addr(worker_id),
        "worker.jax_trace",
        {"duration_s": duration_s, "trace_dir": trace_dir},
        timeout=duration_s + 60,
    )
