"""The paged decode program of a family with a state a session and no experts against its roofline: the bytes and operations a decode step needs (the family's decode_step at the mean batch of the llm.decode_step spans that carry state_slots and the context of the tokens received in the traced window: every weight once, each live session's state and tails once in and once out, the live rows) over the chip's peaks, over the mean device time of the runs of jit_paged_decode in the trace. The program is found by its name (PR 26), not by the host span before it (PERF.md section 7, PR 29). None without a trace, peaks, runs of the program, spans that carry state_slots (a family without a state, a commit from before the family), or where the spans carry experts_touched (a family with experts has its own reader, which counts the experts a step touched)."""

from benchmarks import flops_bytes, harness, kind_kernel


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    found = kind_kernel.traced_steps(records)
    if found is None:
        return None
    trace, steps = found
    runs = kind_kernel.decode_runs(trace)
    steps = [x for x in steps if "state_slots" in x and "experts_touched" not in x]
    if not runs or not steps:
        return None
    t0 = trace["t0_wall"]
    t1 = t0 + trace["window_s"]
    batch = sum(x["batch"] for x in steps) / len(steps)
    # Context each decode step reads: a token received as the k-th of its
    # request was computed against prompt + k positions.
    context = sum(
        r["prompt_tokens"] + k
        for r in records["requests"]
        for k, t in enumerate(r["tokens"]) if t0 <= t < t1
    ) / len(steps)
    config = records["config"]
    ops, nbytes = harness.family(config).decode_step(config, batch, context)
    share, _bound = flops_bytes.roofline_pct(ops, nbytes, sum(runs) / len(runs), records["peaks"])
    return share, "%"
