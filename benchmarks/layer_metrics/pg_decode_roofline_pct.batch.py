"""The paged decode program against its roofline: the bytes (and operations) a decode step needs, from the family's decode_step, over the chip's peaks, over the program's device time in the trace. Memory-bound at these batch sizes."""

from benchmarks import flops_bytes, harness, stats, trace_reduce


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    trace = records["trace"]
    if trace is None or trace.get("t0_wall") is None:
        return None
    runs = trace_reduce.runs_of_phase(trace, records["spans"], "llm.decode_step")
    if not runs:
        return None
    t0 = trace["t0_wall"]
    t1 = t0 + trace["window_s"]
    steps = stats.spans_in(records["spans"], "llm.decode_step", t0, t1)
    if not steps:
        return None
    batch = sum(s["extra"]["batch"] for s in steps) / len(steps)
    # Context each decode step reads: a token received as the k-th of its
    # request was computed against prompt + k positions.
    context = sum(
        r["prompt_tokens"] + k
        for r in records["requests"]
        for k, t in enumerate(r["tokens"]) if t0 <= t < t1
    ) / len(steps)
    ops, nbytes = harness.family(records["config"]).decode_step(records["config"], batch, context)
    share, _bound = flops_bytes.roofline_pct(ops, nbytes, sum(runs) / len(runs), records["peaks"])
    return share, "%"
