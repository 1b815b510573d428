"""Picks that land on an expert held here over all picks of the decode steps' tokens (experts per token x expert layers x batch): held / routed under even routing (25% with 64 of 256), so it reads the routing's imbalance towards or away from this chip's share. From the picks_here / picks fields of llm.decode_step spans."""

from benchmarks import moe_spans


def read(records):
    return moe_spans.share_pct(records, "picks_here", "picks")
