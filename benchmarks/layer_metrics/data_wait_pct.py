"""Share of the window the train loop spent waiting for its next batch: train.data_wait spans over the window."""

from benchmarks import stats


def read(records):
    t0, t1 = records["window"]
    waits = stats.spans_in(records["spans"], "train.data_wait", t0, t1)
    return 100.0 * sum(s["dur_s"] for s in waits) / (t1 - t0), "%"
