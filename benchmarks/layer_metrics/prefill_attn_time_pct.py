"""The share of the prefill programs' device time spent inside the prefill attention kernel: 100 x the seconds of the operations whose name begins paged_prefill_attention (every layer kind's calls) over the seconds of the runs of jit_paged_prefill, both over the traced window. Lower is better, as for the other shares of device time: a faster kernel lowers it, and what is left of a chunk is everything else the chunk does. None without a trace, runs of the program or such operations (a program whose attention was built as the fold has none, and so has a commit from before the kernel)."""

from benchmarks import kind_kernel

PREFILL, KERNEL = "jit_paged_prefill", "paged_prefill_attention"


def read(records):
    trace = records["trace"]
    if trace is None:
        return None
    runs = [dur_ns / 1e9 for name, _start, dur_ns in trace["program_runs"] if name.startswith(PREFILL)]
    kernel_s = kind_kernel.op_seconds(trace, KERNEL)
    if not runs or not kernel_s:
        return None
    return 100.0 * kernel_s / sum(runs), "%"
