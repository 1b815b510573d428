"""Rows of the latent pool that a decode step's attention needs (each live slot's position + 1) over the rows its program reads a layer (every slot's whole table while decode gathers it; the live blocks once a kernel walks the table): 100 x sum(latent_rows_live) / sum(latent_rows_read) over the llm.decode_step spans of the traced window, or of the whole window where nothing was traced. It is what a decode that reads live blocks only moves towards 100. None where the spans carry no such field, as for a family without a latent pool and on a commit from before the fields."""

from benchmarks import moe_spans


def read(records):
    return moe_spans.share_pct(records, "latent_rows_live", "latent_rows_read")
