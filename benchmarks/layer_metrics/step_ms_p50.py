"""Median time from one step's report to the next (train.step spans), which the bounded report ring paces to the device."""

from benchmarks import stats


def read(records):
    steps = stats.spans_in(records["spans"], "train.step", *records["window"])
    if not steps:
        return None
    return stats.percentile([s["dur_s"] * 1e3 for s in steps], 50), "ms"
