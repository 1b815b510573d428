"""Median wait of a request between its hand-over to the engine's pending list and the start of the admission that took it (llm.queue spans): the step in flight, earlier prefills, a slot and blocks."""

from benchmarks import span_readers


def read(records):
    return span_readers.phase_ms_p50(records, "llm.queue")
