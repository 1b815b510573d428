"""The decode attention kernel against its roofline: the least time the chip could take to read the rows of keys and values that a decode step's attention needs (the family's attention_decode fed the llm.decode_step spans' kv_rows_full, the sum of position + 1 a layer that keeps everything, and kv_rows_window, the sum of min(position + 1, window) a window layer; each row's key and value once, scores and values over it), averaged over the steps of the traced window, over the device time a step spends in the operations named paged_decode_attention (one Mosaic call a layer inside jit_paged_decode: their time in the trace over the runs of that program). It is the share of its bytes' speed that the walk over live blocks reaches, the windowed walk and the whole one together. None without a trace, peaks, such operations (a program built with the gather has none), or spans that carry the rows."""

from benchmarks import flops_bytes, harness, stats

DECODE, KERNEL = "jit_paged_decode", "paged_decode_attention"


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    trace = records["trace"]
    if trace is None or trace.get("t0_wall") is None:
        return None
    family = harness.family(records["config"])
    if not hasattr(family, "attention_decode"):
        return None
    runs = sum(name.startswith(DECODE) for name, _start, _dur in trace["program_runs"])
    kernel_s = sum(s for name, s in trace["ops"] if name.startswith(KERNEL))
    t0 = trace["t0_wall"]
    steps = [
        s["extra"] for s in stats.spans_in(records["spans"], "llm.decode_step", t0, t0 + trace["window_s"])
        if "kv_rows_window" in s["extra"]
    ]
    if not runs or not kernel_s or not steps:
        return None
    least = []
    for x in steps:
        ops, nbytes = family.attention_decode(records["config"], x["kv_rows_full"], x["kv_rows_window"])
        least.append(flops_bytes.roofline_pct(ops, nbytes, 1.0, records["peaks"])[0] / 100.0)  # seconds
    return 100.0 * (sum(least) / len(least)) / (kernel_s / runs), "%"
