"""Blocks of the window layers' part of the pool that the occupied slots hold (window_blocks_held) over the blocks they would hold had none been given back (blocks_full_retention: every block they were ever given): 100 x the one sum over the other, over the llm.decode_step spans of the traced window, or of the whole window where nothing was traced. The allocator's release on the record: lower is better, and 100 means that nothing was given back while requests ran. None where the spans carry no such field, as for a family without window layers and on a commit from before the fields."""

from benchmarks import moe_spans


def read(records):
    return moe_spans.share_pct(records, "window_blocks_held", "blocks_full_retention")
