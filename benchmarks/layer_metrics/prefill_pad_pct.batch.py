"""Of the rows the window's prefill programs computed (padded: the sum of their buckets, counted in the engine's _run_prefill), the share that was padding: 100 x (padded - tokens) / padded over the llm.admit_wave spans of the measured window. What a ladder of buckets derived from the prompts the engine sees takes away."""

from benchmarks import launch_pairs

read = launch_pairs.pad_pct
