"""The benchmark's probes inside the process that holds the chip, and the
replica a serve cell deploys: the program's ``LLMServer`` with those probes
added (the train worker calls the same functions from its loop). Nothing of the serving path is overridden; the
extra methods only read (compile events, allocator, engine counters, the
flight recorder's rings) and start and stop the profiler in the one process
that can trace the chip.
"""

from __future__ import annotations

import os
import time

from ray_tpu.llm.serve_llm import LLMServer

HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileEvents:
    """Wall-clock times of this process's compile-cache hits and misses and
    of every trip through the backend compile path (a hit makes one too)."""

    def __init__(self):
        import jax.monitoring

        self.events: list = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == HIT:
            self.events.append([time.time(), "cache_hit"])
        elif event == MISS:
            self.events.append([time.time(), "cache_miss"])

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.events.append([time.time(), "backend_compile"])


def device_report() -> dict:
    import jax

    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
        "bytes_limit": max(s.get("bytes_limit", 0) for s in stats),
    }


def start_trace(log_dir: str) -> int:
    """Start the profiler (device ops, no Python tracer) and write the clock
    anchor: a host event whose wall time is returned, so that host spans can
    be laid on the trace's clock."""
    import jax

    from benchmarks.trace_reduce import CLOCK_ANCHOR

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(CLOCK_ANCHOR):
        wall_ns = time.time_ns()
        time.sleep(0.001)
    return wall_ns


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def flightrec_spans(planes) -> tuple:
    """(this process's flight-recorder events on the wall clock, the count
    each ring has overwritten)."""
    from ray_tpu.util import flightrec

    snap = flightrec.snapshot(planes=planes)
    shift = snap["wall_anchor"] - snap["mono_anchor"]
    out = []
    for ring in snap["rings"].values():
        for e in ring["events"]:
            out.append({
                "phase": e["phase"], "t": e["t"] + shift, "dur_s": e["dur_s"],
                "extra": e.get("extra", {}),
            })
    dropped = {p: r["dropped"] for p, r in snap["rings"].items()}
    return sorted(out, key=lambda s: s["t"]), dropped


class BenchLLMServer(LLMServer):
    def __init__(self, config):
        self._bench_compiles = CompileEvents()  # before the engine compiles
        super().__init__(config)

    def bench_report(self) -> dict:
        import jax

        spans, dropped = flightrec_spans(("llm", "serve"))
        nbytes = lambda tree: sum(x.nbytes for x in jax.tree.leaves(tree))  # noqa: E731
        return {
            "pid": os.getpid(),
            "device": device_report(),
            "stats": dict(self.engine.stats),
            "compile_events": list(self._bench_compiles.events),
            "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
            "spans": spans,
            "spans_dropped": dropped,
            "weight_bytes": nbytes(self.engine.params),
            "pool_bytes": nbytes(self.engine.pool),
            "kv_blocks": self.engine.block_mgr.num_blocks,
        }

    def bench_trace_start(self, log_dir: str) -> int:
        return start_trace(log_dir)

    def bench_trace_stop(self) -> None:
        stop_trace()
