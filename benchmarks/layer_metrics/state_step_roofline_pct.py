"""The state step kernel's calls of a decode step against their roofline: the least time the chip could take to bring the live sessions' float32 states in once and out once (the llm.decode_step span's state_slots x the family's state_step_bytes_per_slot x 2, averaged over the traced steps, over the chip's memory bandwidth), over the device time a step spends in the operations named state_step_ssd (one Mosaic call a Mamba-2 layer inside jit_paged_decode). The small operands and the tails are left out of the bytes, so the share reads a little low and never high. None without a trace, peaks, such operations (the plain step, a family without the kernel, a commit from before the family), spans that carry state_slots, or a family that does not give the bytes."""

from benchmarks import harness, kind_kernel

STATE_STEP = "state_step_ssd"


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    found = kind_kernel.traced_steps(records)
    family = harness.family(records["config"])
    if found is None or not hasattr(family, "state_step_bytes_per_slot"):
        return None
    trace, steps = found
    slots = [x["state_slots"] for x in steps if "state_slots" in x]
    runs = len(kind_kernel.decode_runs(trace))
    kernel_s = kind_kernel.op_seconds(trace, STATE_STEP)
    if not runs or not kernel_s or not slots:
        return None
    nbytes = 2 * family.state_step_bytes_per_slot(records["config"]) * sum(slots) / len(slots)
    return 100.0 * (nbytes / records["peaks"]["hbm_bytes_per_s"]) / (kernel_s / runs), "%"
