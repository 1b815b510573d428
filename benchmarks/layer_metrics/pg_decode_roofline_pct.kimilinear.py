"""The paged decode program of a family with routed experts against its roofline: the bytes a decode step needs (the family's decode_step, with the held experts a step touched as the program counted them on its llm.decode_step spans, not as uniform routing would have it) over the chip's peaks, over the mean device time of the runs of jit_paged_decode in the trace. The program is found by its name (PR 26), not by the host span before it: where a launch follows its span's start by 0.1-0.3 ms, as here, the clock anchor's drift pairs some decode spans with the prefill that follows (PERF.md section 7, PR 29). None where the spans carry no experts_touched field, as on a commit from before it."""

from benchmarks import flops_bytes, harness, stats

DECODE = "jit_paged_decode"


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    trace = records["trace"]
    if trace is None or trace.get("t0_wall") is None:
        return None
    runs = [dur_ns / 1e9 for name, _start, dur_ns in trace["program_runs"] if name.startswith(DECODE)]
    t0 = trace["t0_wall"]
    t1 = t0 + trace["window_s"]
    steps = [
        s["extra"] for s in stats.spans_in(records["spans"], "llm.decode_step", t0, t1)
        if "experts_touched" in s["extra"]
    ]
    if not runs or not steps:
        return None
    batch = sum(x["batch"] for x in steps) / len(steps)
    touched = sum(x["experts_touched"] for x in steps) / len(steps)
    # Context each decode step reads: a token received as the k-th of its
    # request was computed against prompt + k positions.
    context = sum(
        r["prompt_tokens"] + k
        for r in records["requests"]
        for k, t in enumerate(r["tokens"]) if t0 <= t < t1
    ) / len(steps)
    config = records["config"]
    ops, nbytes = harness.family(config).decode_step(config, batch, context, touched=touched)
    share, _bound = flops_bytes.roofline_pct(ops, nbytes, sum(runs) / len(runs), records["peaks"])
    return share, "%"
