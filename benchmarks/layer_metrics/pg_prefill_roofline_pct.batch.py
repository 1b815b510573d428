"""The paged prefill programs of a closed-loop cell against their roofline: the least time the chip could take for the operations and bytes that each prefill of the traced window needs (the family's prefill at the tokens its llm.prefill span carries, and, where the span carries experts_touched and the family's prefill takes it, the held experts those tokens reached as the program counted them), averaged over those prefills, over the mean device time of the runs of jit_paged_prefill in the trace. The programs are found by name (PR 26), every bucket alike, and not by the host span before them (PERF.md section 7, PR 29); the two means are over the same seconds, so a span whose program falls just outside the trace, or the reverse, moves the share by one prefill in some forty. None without a trace, peaks, prefill spans that carry tokens, or runs of the program."""

import inspect

from benchmarks import flops_bytes, harness, stats

PREFILL = "jit_paged_prefill"


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    trace = records["trace"]
    if trace is None or trace.get("t0_wall") is None:
        return None
    runs = [dur_ns / 1e9 for name, _start, dur_ns in trace["program_runs"] if name.startswith(PREFILL)]
    t0 = trace["t0_wall"]
    fills = [
        s["extra"] for s in stats.spans_in(records["spans"], "llm.prefill", t0, t0 + trace["window_s"])
        if "tokens" in s["extra"]
    ]
    if not runs or not fills:
        return None
    config = records["config"]
    prefill = harness.family(config).prefill
    counted = "touched" in inspect.signature(prefill).parameters
    least = []
    for x in fills:
        more = {"touched": x["experts_touched"]} if counted and "experts_touched" in x else {}
        ops, nbytes = prefill(config, x["tokens"], **more)
        share, _bound = flops_bytes.roofline_pct(ops, nbytes, 1.0, records["peaks"])
        least.append(share / 100.0)  # seconds: the share of one second
    return 100.0 * (sum(least) / len(least)) / (sum(runs) / len(runs)), "%"
