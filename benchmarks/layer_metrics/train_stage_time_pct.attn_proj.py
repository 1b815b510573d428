"""The share of the device time of the train step (the program that takes most of the device's time) spent in the attention projections (q/k/v/gate, head norms, rotation, MLA's low-rank pairs and absorbs, the output projection): 100 x the seconds of the operations whose scope path names st.attn_proj (jax.named_scope, ray_tpu/models/common.py:stage; each operation's own time, a fusion whole to the stage its metadata names) over the seconds of the program's runs in the traced window (benchmarks/stage_time.py). Lower is better, as for the other shares of device time: a faster stage lowers its share. 0.0 for a stage the program spent nothing in; None without a trace, runs of the program or any staged operation (a commit from before the stages)."""

from benchmarks import stage_time


def read(records):
    return stage_time.share(records, "train", "attn_proj")
