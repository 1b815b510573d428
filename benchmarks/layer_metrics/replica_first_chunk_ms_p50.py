"""Median time from the entry of the replica's streaming handler to its first yield (serve.replica_first_chunk spans): the engine's queue, admission and prefill, and the hop from the pump to the stream."""

from benchmarks import span_readers


def read(records):
    return span_readers.phase_ms_p50(records, "serve.replica_first_chunk")
