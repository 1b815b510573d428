"""The decode attention kernel's calls in the window layers against their roofline: the least time the chip could take to read the rows of keys and values those layers need in a decode step (the family's attention_decode fed the llm.decode_step span's kv_rows_window, the sum of min(position + 1, window) over the live slots, and no full rows; each row's key and value once for that kind's key/value heads, as the mathematics needs them, whatever the pool pads), averaged over the traced steps, over the device time a step spends in the operations named paged_decode_attention_window (one Mosaic call a window layer inside jit_paged_decode, a slot a grid step). Short walks bound by their starts and not by their bytes: a low share here is the price of a walk's set-up, which the full kind's long walks hide in the lumped reader. None without a trace, peaks, such operations, or spans that carry the rows."""

from benchmarks import kind_kernel


def read(records):
    return kind_kernel.kind_roofline_pct(records, "window", lambda x: (0, x["kv_rows_window"]))
