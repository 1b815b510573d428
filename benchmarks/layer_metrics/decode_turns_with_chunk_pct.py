"""Of the window's decode turns, the share with a slot mid-way through a prefill in chunks (chunks_pending > 0 on the llm.decode_step span): the turns in which prompts wait for their chunks (one chunk a turn at most, and while half the slots decode a chunk only after three turns that ran none), so the share of turns with a backlog of prefill. 100 x the count of such spans over the count of spans that carry the field, over the measured window. None where no span carries it, as for an engine that prefills no prompt in chunks and on a commit from before the field."""

from benchmarks import stats


def read(records):
    steps = [s["extra"] for s in stats.decode_steps(records) if "chunks_pending" in s["extra"]]
    if not steps:
        return None
    return 100.0 * sum(x["chunks_pending"] > 0 for x in steps) / len(steps), "%"
