"""Arithmetic from client records to numbers: percentiles, gaps, TPOT.

A client record is a dict with ``due`` and ``sent`` (wall seconds), ``tokens``
(the wall time each streamed token arrived), ``ok``, ``prompt_tokens``,
``max_tokens`` and ``usage`` (tokens the server says it sent).
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)``: the contract's spread."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def itl_ms(records) -> list:
    """Every gap between consecutive streamed tokens of every request."""
    gaps = []
    for r in records:
        t = r["tokens"]
        gaps.extend((b - a) * 1e3 for a, b in zip(t, t[1:]))
    return gaps


def ttft_ms(records) -> list:
    """First token minus the time the request was due (not sent: a late
    generator must not flatter the server)."""
    return [(r["tokens"][0] - r["due"]) * 1e3 for r in records if r["tokens"]]


def tpot_ms(records) -> list:
    """Per request: time per output token after the first."""
    out = []
    for r in records:
        t = r["tokens"]
        if len(t) >= 2:
            out.append((t[-1] - t[0]) * 1e3 / (len(t) - 1))
    return out


def tokens_in_window(records, t0: float, t1: float) -> int:
    return sum(1 for r in records for t in r["tokens"] if t0 <= t < t1)


def lateness_ms(records) -> list:
    return [(r["sent"] - r["due"]) * 1e3 for r in records]


def spans_in(spans, phase: str, t0: float, t1: float) -> list:
    """Spans of one phase that start inside [t0, t1), on the wall clock."""
    return [s for s in spans if s["phase"] == phase and t0 <= s["t"] < t1]


# -- readings that several per-layer metrics share (one file per metric name,
# each a line or two over these) ------------------------------------------------


def decode_steps(records) -> list:
    return spans_in(records["spans"], "llm.decode_step", *records["window"])


def engine_step_ms_p50(records):
    steps = decode_steps(records)
    if not steps:
        return None
    return percentile([s["dur_s"] * 1e3 for s in steps], 50), "ms"


def window_compiles(records):
    """Trips through the backend compile path, inside the window, that the
    persistent cache did not serve (a hit makes such a trip too)."""
    t0, t1 = records["window"]
    inside = [kind for t, kind in records["compile_events"] if t0 <= t < t1]
    return max(0, inside.count("backend_compile") - inside.count("cache_hit")), "programs"


def device_idle_pct(records):
    trace = records["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"]), "%"
