"""Held experts that a decode step touches (at least one of the step's tokens picks them) over experts held, over the expert layers and the traced window's steps: it sets the expert bytes a step must read. From the experts_touched / experts_held fields of llm.decode_step spans."""

from benchmarks import moe_spans


def read(records):
    return moe_spans.share_pct(records, "experts_touched", "experts_held")
