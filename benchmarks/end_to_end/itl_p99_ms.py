"""The 99th percentile of every gap between consecutive streamed tokens of every request, at the client."""

from benchmarks import stats


def read(records):
    return stats.percentile(stats.itl_ms(records["requests"]), 99), "ms"
