"""Model FLOP/s utilisation: the operations forward and backward require per token (flops_bytes.py; recomputation not counted) times tokens per second, over chips times the bf16 peak."""

from benchmarks import flops_bytes


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    t = records["train"]
    t0, t1 = records["window"]
    c, job = records["config"], records["traffic"]
    per_token = flops_bytes.gpt2_train_flops_per_token(
        c, job["seq_len"], c["assumed"]["padded_vocab_size"]
    )
    rate = t["steps"] * t["tokens_per_step"] / (t1 - t0)
    peak = t["device"]["count"] * records["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_token * rate / peak, "%"
