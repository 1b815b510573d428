"""The decode attention kernel's calls of one layer kind, read from a device
trace: a family whose kinds of attention layer differ names each kind's calls
(a suffix behind the kernel's own name), and the per-layer metrics that tell
the kinds apart are a line each over these. None where the trace holds no
such operation, as for a family that names no kind, a program built with the
gather, and a commit from before the names.
"""

from __future__ import annotations

from benchmarks import flops_bytes, harness, stats

DECODE, KERNEL = "jit_paged_decode", "paged_decode_attention"


def traced_steps(records):
    """``(the reduced trace, the llm.decode_step spans' fields inside its
    window)``, or None without a trace on the wall clock."""
    trace = records["trace"]
    if trace is None or trace.get("t0_wall") is None:
        return None
    t0 = trace["t0_wall"]
    steps = stats.spans_in(records["spans"], "llm.decode_step", t0, t0 + trace["window_s"])
    return trace, [s["extra"] for s in steps]


def op_seconds(trace, prefix: str) -> float:
    return sum(s for name, s in trace["ops"] if name.startswith(prefix))


def decode_runs(trace) -> list:
    """Device seconds of each run of the decode program in the trace."""
    return [dur_ns / 1e9 for name, _start, dur_ns in trace["program_runs"] if name.startswith(DECODE)]


def kind_roofline_pct(records, kind: str, rows):
    """The calls named after ``kind`` against the least time the chip could
    take for the rows that kind's layers need in a decode step (the family's
    ``attention_decode`` fed ``rows(span fields) -> (rows_full, rows_window)``,
    one of them 0), averaged over the traced steps, over the device time a
    step spends in those calls."""
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    found = traced_steps(records)
    family = harness.family(records["config"])
    if found is None or not hasattr(family, "attention_decode"):
        return None
    trace, steps = found
    steps = [x for x in steps if "kv_rows_window" in x]
    runs = len(decode_runs(trace))
    kernel_s = op_seconds(trace, f"{KERNEL}_{kind}")
    if not runs or not kernel_s or not steps:
        return None
    least = [
        flops_bytes.roofline_pct(*family.attention_decode(records["config"], *rows(x)), 1.0, records["peaks"])[0] / 100.0
        for x in steps
    ]  # seconds
    return 100.0 * (sum(least) / len(least)) / (kernel_s / runs), "%"
