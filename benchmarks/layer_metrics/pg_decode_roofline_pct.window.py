"""The paged decode program of a family whose layers are of two kinds against its roofline: the least time the chip could take for the operations and bytes that each decode step of the traced window needs (the family's decode_step fed what the step's llm.decode_step span carries: batch, kv_rows_full, the rows a layer that keeps everything needs, kv_rows_window, the rows a window layer needs, min(context, window) a slot, and experts_touched as the program counted them), averaged over those steps, over the mean device time of the runs of jit_paged_decode in the trace. One summed context, which is what the other decode readers pass, cannot price min(context, window). The program is found by its name (PR 26), not by the host span before it (PERF.md section 7, PR 29). None without a trace, peaks, runs of the program, or spans that carry kv_rows_window, as on a commit from before the field and for a family without window layers."""

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
    steps = [
        s["extra"] for s in stats.spans_in(records["spans"], "llm.decode_step", t0, t0 + trace["window_s"])
        if "kv_rows_window" in s["extra"] and "experts_touched" in s["extra"]
    ]
    if not runs or not steps:
        return None
    config = records["config"]
    decode_step = harness.family(config).decode_step
    least = []
    for x in steps:
        ops, nbytes = decode_step(
            config, x["batch"], x["kv_rows_full"], touched=x["experts_touched"],
            rows_window=x["kv_rows_window"],
        )
        least.append(flops_bytes.roofline_pct(ops, nbytes, 1.0, records["peaks"])[0] / 100.0)  # seconds
    return 100.0 * (sum(least) / len(least)) / (sum(runs) / len(runs)), "%"
