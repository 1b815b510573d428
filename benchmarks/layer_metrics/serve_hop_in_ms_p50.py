"""Median time from the proxy having read a request to the entry of the replica's handler (serve.hop_in spans on the replica's ring): proxy parse, router admission and pick, the actor call and its deserialising. One host, so one wall clock."""

from benchmarks import span_readers


def read(records):
    return span_readers.phase_ms_p50(records, "serve.hop_in")
