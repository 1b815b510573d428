"""The flash attention kernels against their roofline in the train step: the least time the chip could take for the operations and bytes that causal attention requires, forward and backward, of every layer of one step on one device's shard, over the time the ops named flash_fwd* and flash_bwd* take a step in the trace.

Required, as in flops_bytes.py: a query sees the keys at or before it, so QK^T and PV each cost 2 * head_dim operations for S (S + 1) / 2 pairs a head; the backward's four products (dV, dP, dQ, dK) cost twice the forward, and recomputing the scores counts nothing. Bytes: the forward reads q, k, v and writes o and the row statistics; the backward reads q, k, v, dO and two row statistics and writes dq, dk, dv. Each kernel takes the larger of its compute and its memory time.

Seconds a step: the flash ops' share of the device's busy time in the traced window, times the median device time of one run of the train program (the program that takes most of device 0's time). A share of the whole window, so a step cut by the window's edge counts for what was traced of it."""

from benchmarks import flops_bytes, stats

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def required(config: dict, job: dict):
    """[(operations, bytes)] of the forward and of the backward kernel, one
    layer, one device's shard."""
    mesh = job["mesh"]
    batch = job["global_batch"] / (mesh.get("dp", 1) * mesh.get("fsdp", 1))
    heads = config["n_head"] / mesh.get("tp", 1)
    S, D = job["seq_len"], config["n_embd"] // config["n_head"]
    el = _BYTES[config["dtype"]]
    fwd_ops = batch * heads * 2 * 2 * D * S * (S + 1) / 2
    tensor = batch * heads * S * D * el
    row = batch * heads * S * 4
    return [(fwd_ops, 4 * tensor + row), (2 * fwd_ops, 7 * tensor + 2 * row)]


def read(records):
    trace = records["trace"]
    if trace is None or records["peaks"] is None:
        return None
    flash_s = sum(
        s for name, s in trace["ops"] if name.startswith(("flash_fwd", "flash_bwd"))
    )
    if not flash_s or not trace["programs"]:
        return None
    step_runs = max(trace["programs"].values(), key=sum)
    flash_step_s = flash_s / trace["busy_s"] * stats.percentile(step_runs, 50)
    least_s = records["config"]["n_layer"] * sum(
        flops_bytes.roofline_pct(ops, nbytes, 1.0, records["peaks"])[0] / 100.0
        for ops, nbytes in required(records["config"], records["traffic"])
    )
    return 100.0 * least_s / flash_step_s, "%"
