"""Median gap between two steps of a busy engine (llm.pump_gap spans): from one step's return in the executor thread, through the event loop and the token push, to the next step's entry. Not recorded after the engine ran dry."""

from benchmarks import span_readers


def read(records):
    return span_readers.phase_ms_p50(records, "llm.pump_gap")
