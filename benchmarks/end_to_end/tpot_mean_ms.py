"""Time per output token after the first, per request, averaged over the requests."""

from benchmarks import stats


def read(records):
    per_request = stats.tpot_ms(records["requests"])
    return sum(per_request) / len(per_request), "ms"
