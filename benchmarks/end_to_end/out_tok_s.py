"""Tokens that clients received inside the window, over the window."""

from benchmarks import stats


def read(records):
    t0, t1 = records["window"]
    return stats.tokens_in_window(records["requests"], t0, t1) / (t1 - t0), "tokens/s"
