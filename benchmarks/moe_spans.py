"""Readings of the expert layers' counters that ride the ``llm.decode_step``
spans of a family with routed experts (``picks``, ``picks_here``,
``experts_touched``, ``experts_held``: sums over the expert layers of one
step). Shared by the per-layer metrics that are ratios of two of them; None
where the program records no such field, as a family without experts and a
commit from before the fields do.
"""

from __future__ import annotations

from benchmarks import stats


def share_pct(records, part: str, whole: str):
    """100 x sum(part) / sum(whole) over the decode steps of the traced
    window (the steps the decode program's roofline share is read over), or
    of the whole window where nothing was traced."""
    trace = records["trace"]
    if trace is not None and trace.get("t0_wall") is not None:
        t0 = trace["t0_wall"]
        steps = stats.spans_in(records["spans"], "llm.decode_step", t0, t0 + trace["window_s"])
    else:
        steps = stats.decode_steps(records)
    steps = [s["extra"] for s in steps if whole in s["extra"] and part in s["extra"]]
    total = sum(x[whole] for x in steps)
    if not total:
        return None
    return 100.0 * sum(x[part] for x in steps) / total, "%"
