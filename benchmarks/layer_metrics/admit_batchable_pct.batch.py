"""Of the requests admitted in the measured window, the share admitted in a wave of two or more (the admitted field of llm.admit_wave): what a prefill that batches the prompts waiting together could merge."""

from benchmarks import launch_pairs

read = launch_pairs.batchable_pct
