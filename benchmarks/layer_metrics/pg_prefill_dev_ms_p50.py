"""Median device time of one run of a paged prefill program (any bucket), from the trace; the programs are found by the llm.prefill spans that start them (trace_reduce.runs_of_phase)."""

from benchmarks import stats, trace_reduce


def read(records):
    trace = records["trace"]
    if trace is None:
        return None
    runs = trace_reduce.runs_of_phase(trace, records["spans"], "llm.prefill")
    if not runs:
        return None
    return stats.percentile(runs, 50) * 1e3, "ms"
