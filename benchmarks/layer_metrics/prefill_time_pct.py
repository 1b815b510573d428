"""Share of the engine's device time that goes to prefill: device seconds of the prefill programs over those of the prefill and decode programs, in the traced window. (Device time, not the llm.prefill span: since PR 26 the span ends where the host has the logits, so it also holds the launch and the copy back; prefill_span_ms_p50 reads it.)"""

from benchmarks import trace_reduce


def read(records):
    trace = records["trace"]
    if trace is None:
        return None
    prefill = sum(trace_reduce.runs_of_phase(trace, records["spans"], "llm.prefill"))
    decode = sum(trace_reduce.runs_of_phase(trace, records["spans"], "llm.decode_step"))
    if prefill + decode == 0:
        return None
    return 100.0 * prefill / (prefill + decode), "%"
