"""The decode attention kernel's calls at heads of 64 (a position's value and key of a head side by side in one pool row) against their roofline: the least time the chip could take to read the rows of keys and values a decode step's attention needs (the family's attention_decode fed the live rows, the llm.decode_step span's kv_blocks_live x the block size rounded down to the rows themselves where the span carries them, else the blocks' rows; each row's key and value once at the bytes the mathematics needs, whatever the pool pads), averaged over the traced steps, over the device time a step spends in the operations named paged_decode_attention_packed (one Mosaic call an attention layer inside jit_paged_decode). None without a trace, peaks, such operations (the gather, a family of another layout, a commit from before the layout), spans, or a family without attention_decode."""

from benchmarks import flops_bytes, harness, kind_kernel

KERNEL = "paged_decode_attention_packed"


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    found = kind_kernel.traced_steps(records)
    family = harness.family(records["config"])
    if found is None or not hasattr(family, "attention_decode"):
        return None
    trace, steps = found
    runs = len(kind_kernel.decode_runs(trace))
    kernel_s = kind_kernel.op_seconds(trace, KERNEL)
    steps = [x for x in steps if "kv_blocks_live" in x]
    if not runs or not kernel_s or not steps:
        return None
    t0 = trace["t0_wall"]
    t1 = t0 + trace["window_s"]
    # The rows a step attends: a token received as the k-th of its request was
    # computed against prompt + k positions.
    rows = sum(
        r["prompt_tokens"] + k
        for r in records["requests"]
        for k, t in enumerate(r["tokens"]) if t0 <= t < t1
    ) / len(steps)
    ops, nbytes = family.attention_decode(records["config"], rows)
    least = flops_bytes.roofline_pct(ops, nbytes, 1.0, records["peaks"])[0] / 100.0  # seconds
    return 100.0 * least / (kernel_s / runs), "%"
