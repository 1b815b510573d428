"""Median length of an llm.prefill span that ends where the prefill's logits are on the host (the bucket field marks such a span; before it existed the span ended when the dispatch returned, and this reads nothing). Read beside pg_prefill_dev_ms_p50, the same programs' device time, and so over the same prefills: those of the traced seconds where there is a trace, else those of the whole window."""

from benchmarks import stats


def read(records):
    t0, t1 = records["window"]
    trace = records["trace"]
    if trace is not None and trace.get("t0_wall") is not None:
        t0, t1 = trace["t0_wall"], trace["t0_wall"] + trace["window_s"]
    spans = [
        s for s in stats.spans_in(records["spans"], "llm.prefill", t0, t1)
        if "bucket" in s["extra"]
    ]
    if not spans:
        return None
    return stats.percentile([s["dur_s"] * 1e3 for s in spans], 50), "ms"
