"""Rows of keys and values that a decode step's attention needs in a window layer (each live slot's min(position + 1, window)) over the rows its program reads there (the blocks from the one that holds the window's first position to the one that holds the slot's own, under the kernel's walk with a lower bound; every slot's whole table under the gather): 100 x sum(kv_rows_window) / sum(kv_rows_window_read) over the llm.decode_step spans of the traced window, or of the whole window where nothing was traced. What latent_live_row_pct is to latent rows; a walk that starts at the window moves it towards 100, less the two partly used blocks at a window's ends. None where the spans carry no such field, as for a family without window layers and on a commit from before the fields."""

from benchmarks import moe_spans


def read(records):
    return moe_spans.share_pct(records, "kv_rows_window", "kv_rows_window_read")
