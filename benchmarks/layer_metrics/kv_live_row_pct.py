"""Rows of keys and values that a decode step's attention needs in a layer that keeps every position (each live slot's position + 1) over the rows its program reads there (each live slot's live blocks under the kernel's walk, every slot's whole table under the gather): 100 x sum(kv_rows_live) / sum(kv_rows_read) over the llm.decode_step spans of the traced window, or of the whole window where nothing was traced. What latent_live_row_pct is to latent rows; under the kernel it reads 100 less the partly used block at each slot's end. None where the spans carry no such field, as for a family that records none and on a commit from before the fields."""

from benchmarks import moe_spans


def read(records):
    return moe_spans.share_pct(records, "kv_rows_live", "kv_rows_read")
