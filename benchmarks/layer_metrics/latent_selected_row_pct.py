"""Rows of the latent pool that a decode step's attention keeps (each live slot's min(position + 1, index_topk): what an indexer selected) over the rows its slots hold (each live slot's position + 1): 100 x sum(latent_rows_selected) / sum(latent_rows_live) over the llm.decode_step spans of the traced window, or of the whole window where nothing was traced. It says how sparse the traffic makes attention: 100 where every context is within index_topk and selection does nothing, 12.5 at contexts of 16k under 2,048 kept. Lower is sparser; it moves with the traffic and the configuration, not with the program's speed. None where the spans carry no such field, as for a family without an indexer and on a commit from before the fields."""

from benchmarks import moe_spans


def read(records):
    return moe_spans.share_pct(records, "latent_rows_selected", "latent_rows_live")
