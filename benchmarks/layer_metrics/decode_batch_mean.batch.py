"""Sequences per decode step, averaged over the steps of the window (the batch field of llm.decode_step spans)."""

from benchmarks import stats


def read(records):
    steps = stats.decode_steps(records)
    if not steps:
        return None
    return sum(s["extra"]["batch"] for s in steps) / len(steps), "requests"
