"""The paged prefill programs of a cell that prefills in chunks against their roofline: the least time the chip could take for the operations and bytes that each prefill program of the traced window needs (the family's prefill at the tokens and the start its llm.prefill or llm.prefill_chunk span carries, a whole prompt starting at what it reused, a chunk at its start, so that a chunk's attention is counted over the keys it may see and not as a fresh prompt's; and the held experts those tokens reached, experts_touched, as the program counted them), averaged over those programs, over the mean device time of the runs of jit_paged_prefill in the trace. The programs are found by name (PR 26), every bucket alike; the two means are over the same seconds. None without a trace, peaks, runs of the program, or spans that carry tokens and experts_touched, or for a family whose prefill takes no start."""

import inspect

from benchmarks import flops_bytes, harness, stats

PREFILL = "jit_paged_prefill"


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    trace = records["trace"]
    if trace is None or trace.get("t0_wall") is None:
        return None
    config = records["config"]
    prefill = harness.family(config).prefill
    if "start" not in inspect.signature(prefill).parameters:
        return None
    runs = [dur_ns / 1e9 for name, _start, dur_ns in trace["program_runs"] if name.startswith(PREFILL)]
    t0 = trace["t0_wall"]
    t1 = t0 + trace["window_s"]
    fills = [
        s["extra"] for phase in ("llm.prefill", "llm.prefill_chunk")
        for s in stats.spans_in(records["spans"], phase, t0, t1)
        if "tokens" in s["extra"] and "experts_touched" in s["extra"]
    ]
    if not runs or not fills:
        return None
    least = []
    for x in fills:
        ops, nbytes = prefill(
            config, x["tokens"], touched=x["experts_touched"], start=x.get("start", x.get("reused", 0))
        )
        least.append(flops_bytes.roofline_pct(ops, nbytes, 1.0, records["peaks"])[0] / 100.0)  # seconds
    return 100.0 * (sum(least) / len(least)) / (sum(runs) / len(runs)), "%"
