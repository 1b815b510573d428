"""Model FLOP/s utilisation: the operations forward and backward require per token (the family's train_flops_per_token; recomputation not counted) times tokens per second, over chips times the bf16 peak."""

from benchmarks import harness


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    t = records["train"]
    t0, t1 = records["window"]
    c, job = records["config"], records["traffic"]
    per_token = harness.family(c).train_flops_per_token(c, job)
    rate = t["steps"] * t["tokens_per_step"] / (t1 - t0)
    peak = t["device"]["count"] * records["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_token * rate / peak, "%"
