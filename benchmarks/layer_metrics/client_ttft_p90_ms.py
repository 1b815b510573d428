"""Time to the first token at the client, from when the request was due: percentile 90."""

from benchmarks import stats


def read(records):
    return stats.percentile(stats.ttft_ms(records["requests"]), 90), "ms"
