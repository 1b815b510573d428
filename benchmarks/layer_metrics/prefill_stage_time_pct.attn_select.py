"""The share of the device time of the prefill programs (jit_paged_prefill*) spent in an indexer in front of attention (its three projections, its rotation, the scores of every query against every cached index key and the choice of the positions each query keeps): 100 x the seconds of the operations whose scope path names st.attn_select (jax.named_scope, ray_tpu/models/common.py:stage; each operation's own time, a fusion whole to the stage its metadata names) over the seconds of the program's runs in the traced window (benchmarks/stage_time.py). Lower is better, as for the other shares of device time: a faster stage lowers its share. 0.0 for a stage the program spent nothing in; None without a trace, runs of the program or any staged operation (a commit from before the stages)."""

from benchmarks import stage_time


def read(records):
    return stage_time.share(records, "prefill", "attn_select")
