"""The decode attention kernel's calls in the layers that keep every position against their roofline: the least time the chip could take to read the rows of keys and values those layers need in a decode step (the family's attention_decode fed the llm.decode_step span's kv_rows_full, the sum of position + 1 over the live slots, and no window rows; each row's key and value once for that kind's key/value heads, as the mathematics needs them, whatever the pool pads), averaged over the traced steps, over the device time a step spends in the operations named paged_decode_attention_full (one Mosaic call a full layer inside jit_paged_decode). A long walk bound by its bytes; the lumped attn_decode_kernel_roofline_pct hides it behind the window kind's many short ones. None without a trace, peaks, such operations (a family that names no kind, the gather, a commit from before the names), or spans that carry the rows."""

from benchmarks import kind_kernel


def read(records):
    return kind_kernel.kind_roofline_pct(records, "full", lambda x: (x["kv_rows_full"], 0))
