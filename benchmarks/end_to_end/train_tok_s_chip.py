"""Training tokens per second per chip: every step of the window over all of its time, closed with block_until_ready."""

def read(records):
    t = records["train"]
    t0, t1 = records["window"]
    return t["steps"] * t["tokens_per_step"] / (t1 - t0) / t["device"]["count"], "tokens/s/chip"
