"""Median host time inside one decode step: llm.decode_dispatch (uploads of last tokens, positions and block tables, and the launch) plus llm.decode_sample (sampling request by request), per step of the window. What is left of the step, llm.decode_readback, waits for the device."""

from benchmarks import span_readers

read = span_readers.decode_host_ms_p50
