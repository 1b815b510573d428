"""Readings of the flight recorder's spans that several per-layer metrics
share (one file per metric name under ``layer_metrics/``, each a line over
these). ``records["spans"]`` are the events of the process that holds the
chip, on the wall clock; every reading keeps to the measured window and
returns None where the program records no such phase, as a commit from
before the phase existed does.
"""

from __future__ import annotations

from benchmarks import stats


def phase_ms_p50(records, phase: str):
    """Median length, in ms, of the spans of ``phase`` that start inside
    the window."""
    spans = stats.spans_in(records["spans"], phase, *records["window"])
    if not spans:
        return None
    return stats.percentile([s["dur_s"] * 1e3 for s in spans], 50), "ms"


def decode_host_ms_p50(records):
    """Median, over the decode steps of the window, of the host's two parts
    of a step: ``llm.decode_dispatch`` (three uploads and the launch) plus
    ``llm.decode_sample`` (sampling and bookkeeping, request by request).
    The third part, ``llm.decode_readback``, is the wait for the device.
    The engine records a step's parts in order from one thread, so a sample
    belongs to the dispatch before it."""
    t0, t1 = records["window"]
    host, dispatch = [], None
    for s in records["spans"]:  # sorted by start
        if s["phase"] == "llm.decode_dispatch":
            dispatch = s
        elif s["phase"] == "llm.decode_sample" and dispatch is not None:
            if t0 <= dispatch["t"] < t1:
                host.append((dispatch["dur_s"] + s["dur_s"]) * 1e3)
            dispatch = None
    if not host:
        return None
    return stats.percentile(host, 50), "ms"
